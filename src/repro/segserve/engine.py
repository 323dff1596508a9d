"""Tiled-segmentation serving engine: request queue + slot table +
shape/class-grouped micro-batching, with per-image energy accounting.

The LM engine's loop, re-based on image tiles: requests (arbitrary-size
images) wait in a FIFO, a bounded slot table caps in-flight stitching
canvases, and the unit of batched work is a *micro-batch of tiles* instead
of one token per sequence.  Tiles are grouped by

    (input window shape, budget class, image amplitude octave)

and packed into fixed-size batches (padded with zero tiles), so the jit
cache holds one executable per group signature — a handful per image
geometry, reused across every request — and inside each executable the
static per-layer plane counts hit the same
``kernels.mma_matmul.plane_variant`` specializations.  Groups freely mix
tiles of different requests: micro-batching across the queue is the whole
point of the slot table.

Accounting mirrors the LM engine's energy story, per *image*: relation-(2)
cycles of every tile the image consumed (halo overhead included, priced
honestly) under its refined schedule, against the useful whole-canvas ops
— time, GOPS and GOPS/W at the paper's implied accelerator power.  These
are FPGA-model figures, not measurements of the device running the engine.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cycle_model as cm
from repro.core import energy_model as em
from repro.core.plane_schedule import PlaneSchedule
from repro.models import unet
from repro.serve.queue import FifoQueue, SlotTable

from . import adaptive, tiling

_IMPLIED_POWER_W = (
    cm.PAPER_TABLE1["proposed"]["gops"] / cm.PAPER_TABLE1["proposed"]["gops_w"]
)


SPAN_PREFIX = "segserve."


def _span(name: str, **meta):
    """A host span on the profiler's clock, the one the device's ops are
    stamped on (``jax.profiler.TraceAnnotation``).  With no profiler
    listening it costs one flag check; ``meta`` lands on the span as stats
    (``rid=``, a step's window shape, class and tile count)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **meta)


@dataclass
class SegCounters:
    """Counts of the engine's work since it was built, always kept (one
    integer add each).  ``steps`` counts collected micro-batches; ``tiles
    / tile_slots`` is the micro-batch fill, ``window_pixels`` the
    input-window pixels run (halo included), and ``host_syncs`` the
    blocking device-to-host reads, one per collected micro-batch.
    ``launched_ahead`` counts the launches made while an earlier
    micro-batch was still uncollected, so ``launched_ahead / steps`` is
    the share of micro-batches the device started before the host had
    fetched and stitched the one before."""

    admitted: int = 0
    completed: int = 0
    steps: int = 0
    tiles: int = 0
    tile_slots: int = 0  # steps x batch
    window_pixels: int = 0
    host_syncs: int = 0
    launched_ahead: int = 0
    upload_bytes: int = 0
    fetch_bytes: int = 0
    executables: int = 0  # distinct (in_h, in_w, class) signatures run


@functools.lru_cache(maxsize=2)
def _shared_forward(per_tile_quant: bool):
    """Process-wide jitted tile forward, shared by every engine instance so
    repeated engine construction (the autotuner's certify loop, the bench's
    row sweep) reuses one compile cache instead of re-tracing per engine.

    ``per_tile_quant=True`` vmaps the forward over the micro-batch, so the
    dynamic activation quantization inside sees one tile at a time: each
    tile gets its *own* int8 scale, numerics stop depending on which tiles
    happened to share a batch, and the per-tile certificate of a
    :class:`~repro.autotune.plan.TunedPlan` (computed on single windows)
    transfers to the batched serving path exactly."""
    if per_tile_quant:
        def fwd(params, x, cfg):
            return jax.vmap(
                lambda xi: unet.forward(params, xi[None], cfg)[0]
            )(x)

        return jax.jit(fwd, static_argnums=2)
    return jax.jit(unet.forward, static_argnums=2)


@dataclass
class SegResult:
    """One served image: stitched logits + the modeled energy account.

    Every figure besides ``logits`` is modeled for the paper's FPGA, not
    measured: ``cycles`` are relation-(2) cycles, ``time_ms`` is those
    cycles at the FPGA's 100 MHz clock, ``gops_per_w`` and ``energy_mj``
    apply the paper's implied accelerator power, and ``pj`` is the FPGA pJ
    model.  None of them is time or energy on the device that ran the
    forward."""

    logits: np.ndarray  # (H, W, n_classes) f32
    cycles: int
    ops: int
    n_tiles: int
    class_counts: dict[int, int]  # budget class -> tile count
    pj: int = 0  # metered active energy: tile cycles at their plane rates

    @property
    def time_ms(self) -> float:
        return self.cycles / cm.FREQ_HZ * 1e3

    @property
    def gops(self) -> float:
        return self.ops / (self.time_ms * 1e-3) / 1e9

    @property
    def gops_per_w(self) -> float:
        return self.gops / _IMPLIED_POWER_W

    @property
    def energy_mj(self) -> float:
        return _IMPLIED_POWER_W * self.time_ms

    @property
    def metered_mj(self) -> float:
        return em.pj_to_mj(self.pj)

    @property
    def metered_gops_per_w(self) -> float | None:
        return em.metered_gops_per_w(self.ops, self.pj)


@dataclass(frozen=True)
class TileEvent:
    """One emitted tile: the progressive-display unit of the streaming API.

    Under priority scheduling the engine emits an image's structure-class
    tiles (low ``klass`` — full-amplitude, many-plane regions) before its
    background tiles, so a caller consuming events sees the clinically
    interesting content first; ``request.partial()`` is the stitch so far.
    ``cycles`` is the tile's relation-(2) price at its class schedule — the
    currency the serving gateway charges micro-batches against its round
    budget in.  ``pj`` is the same work priced in integer picojoules: each
    layer's cycles at that layer's plane-proportional rate, so narrower
    budget classes are cheaper per cycle, not just shorter.
    """

    rid: int
    tile: int  # index into request.plan.tiles
    klass: int  # budget class (0 = structure / full amplitude)
    cycles: int
    core: tuple[int, int, int, int]  # (y0, x0, y1, x1) canvas coords
    done: bool  # this emission completed the request
    request: "SegRequest"
    pj: int = 0


@dataclass
class SegRequest:
    rid: int
    image: np.ndarray  # (H, W, C)
    # scheduling label: tiles of different groups never share a micro-batch,
    # so a caller (the gateway) can step one group's work under its own
    # cycle quantum without charging it for another group's tiles
    group: str | None = None
    # filled at admission
    plan: tiling.TilePlan | None = None
    slot: int = -1
    canvas_in: np.ndarray | None = None
    canvas_out: np.ndarray | None = None
    remaining: int = 0
    cycles: int = 0
    pj: int = 0
    ops: int = 0
    class_counts: dict[int, int] = field(default_factory=dict)
    emitted: list[int] = field(default_factory=list)  # tile emission order
    result: SegResult | None = None

    @property
    def done(self) -> bool:
        return self.result is not None

    def partial(self) -> np.ndarray:
        """The progressive stitch so far: emitted cores hold their final
        logits (stitching is a disjoint scatter, so early tiles are exact),
        unemitted cores are zero.  After completion this is the final
        result's logits."""
        if self.result is not None:
            return self.result.logits
        if self.canvas_out is None:
            raise ValueError(f"request {self.rid} not yet admitted")
        return self.canvas_out[: self.plan.h, : self.plan.w].copy()


class SegEngine:
    """Micro-batching executor for U-Net segmentation requests.

    Args:
      cfg: the :class:`~repro.models.unet.UNetConfig` to serve (its
        ``plane_schedule`` / ``planes`` is the certified layer-level
        policy; ``quant_mode='none'`` serves the float datapath and makes
        tiling bit-comparable to the whole-image forward).
      params: U-Net params for ``cfg``.
      tile: core stride (multiple of ``2**depth``).
      halo: exact by default (:func:`~repro.segserve.tiling.halo_for`);
        smaller halos (down to 0) are legal and approximate: each tile's
        convs zero-pad at the cut, so seams carry error.
      batch: fixed tile micro-batch size (short groups are zero-padded).
      max_active: slot-table capacity — concurrent stitching canvases.
      adaptive: refine the layer schedule per budget class (quantized
        datapath only).
      max_class: amplitude-octave cap for flat/empty tiles.
      plan: a :class:`~repro.autotune.plan.TunedPlan` — overrides ``tile``
        and ``halo`` with the tuned geometry (validated through
        ``cfg.validate_tile``), classifies tiles by the *calibrated*
        thresholds instead of fixed octaves, runs each class at the plan's
        measured-ratio refined schedule, and switches the quantized
        datapath to per-tile activation scales so the plan's certificate
        transfers to the batched path exactly.
      priority: prefill-style tile prioritization — pick the pending
        micro-batch group with the *lowest* budget class first (structure
        before background), so progressive consumers (:class:`TileEvent`
        stream, ``SegRequest.partial``) see the high-information regions
        early.  Scheduling order only: group membership and within-group
        packing are fixed at admission, so the final stitch is
        bit-identical to the ``priority=False`` (admission-order) path
        whenever numerics are batch-composition independent — always under
        a tuned ``plan`` (per-tile quantization) or the float datapath,
        and on the batch-shared-scale quantized path whenever the
        admission sequence itself is unchanged (e.g. requests <=
        ``max_active``).  With shared scales *and* slot churn, reordering
        can shift which requests' same-key tiles share a batch, which
        legitimately moves low-bit rounding.
    """

    def __init__(
        self,
        cfg: unet.UNetConfig,
        params,
        *,
        tile: int = 32,
        halo: int | None = None,
        batch: int = 4,
        max_active: int = 4,
        adaptive: bool = True,
        max_class: int = adaptive.MAX_CLASS,
        plan=None,
        priority: bool = True,
    ):
        self.cfg = cfg
        self.params = params
        self.plan = plan
        if plan is not None:
            if getattr(plan, "workload", "unet") != "unet":
                raise ValueError(
                    f"cannot serve a {plan.workload!r} plan through the "
                    f"segmentation engine"
                )
            if len(plan.planes) != len(cfg.conv_layers()):
                raise ValueError(
                    f"plan covers {len(plan.planes)} convs but this "
                    f"geometry has {len(cfg.conv_layers())}"
                )
            # the halo walk's geometry guard, through UNetConfig validation
            tile = cfg.validate_tile(int(plan.tile), halo=int(plan.halo))
            halo = int(plan.halo)
        mult = 2**cfg.depth
        if tile < mult or tile % mult:
            raise ValueError(
                f"tile {tile} must be a positive multiple of 2**depth = {mult}"
            )
        if halo is not None and halo < 0:
            raise ValueError(f"halo {halo} < 0")
        if batch < 1:
            raise ValueError(f"batch {batch} < 1")
        self.tile = tile
        self.halo = halo
        self.batch = batch
        self.priority = priority
        quantized = cfg.quant_mode == "mma_int8"
        self.adaptive = adaptive and quantized and (
            plan is None or plan.class_thresholds is not None
        )
        self.max_class = max_class
        if plan is not None and quantized:
            self.base_schedule = plan.schedule()
        elif quantized:
            self.base_schedule = cfg.schedule()
        else:
            self.base_schedule = PlaneSchedule.uniform(
                8, len(cfg.conv_layers())
            )
        self.queue: FifoQueue[SegRequest] = FifoQueue()
        self.slots: SlotTable[SegRequest] = SlotTable(max_active)
        # (in_h, in_w, class, amax_octave) -> [(request, tile_index), ...]
        self._tasks: dict[tuple[int, int, int, int], list] = {}
        # micro-batches dispatched and not yet collected, oldest first:
        # (key, [(request, tile_index), ...], device output)
        self._inflight: collections.deque = collections.deque()
        self._fwd = _shared_forward(plan is not None and quantized)
        self._cfg_for_class: dict[int, unet.UNetConfig] = {}
        self._pj_cache: dict[tuple[int, int, int], int] = {}
        self._next_rid = 0
        self.counters = SegCounters()
        self._signatures: set[tuple[int, int, int]] = set()

    # ----------------------------------------------------------- schedules

    def _class_planes(self, k: int) -> tuple[int, ...]:
        """Per-layer budgets class-``k`` micro-batches run: the plan's
        calibrated table, else the octave-heuristic refinement."""
        if self.plan is not None:
            return tuple(self.plan.class_schedule(k))
        return adaptive.class_schedule(self.base_schedule, k).planes

    def class_cfg(self, k: int) -> unet.UNetConfig:
        """The (static, jit-cache-keyed) config class-``k`` batches run."""
        if k not in self._cfg_for_class:
            cfg = self.cfg
            if cfg.quant_mode == "mma_int8":
                cfg = dataclasses.replace(
                    cfg, plane_schedule=self._class_planes(k)
                )
            self._cfg_for_class[k] = cfg
        return self._cfg_for_class[k]

    def _tile_cycles(self, in_h: int, in_w: int, k: int) -> int:
        """Relation-(2) cycles of one (in_h, in_w) tile at class ``k``."""
        return cm.unet_window_cycles(
            (in_h, in_w), self.cfg.in_ch, self.cfg.base, self.cfg.depth,
            self.cfg.convs_per_stage, self._class_planes(k),
        )

    def _tile_pj(self, in_h: int, in_w: int, k: int) -> int:
        """Metered active energy of one (in_h, in_w) tile at class ``k``:
        the same relation-(2) layer cycles as :meth:`_tile_cycles`, each
        priced at its layer's plane rate (integer pJ).  Memoized like the
        cycle price — thousands of tiles share a handful of signatures."""
        key = (in_h, in_w, k)
        pj = self._pj_cache.get(key)
        if pj is None:
            layers = cm.unet_conv_layers(
                (in_h, in_w), self.cfg.in_ch, self.cfg.base, self.cfg.depth,
                self.cfg.convs_per_stage,
            )
            pj = em.schedule_pj(layers, self._class_planes(k))
            self._pj_cache[key] = pj
        return pj

    # ------------------------------------------------------------ admission

    def submit(self, image: np.ndarray, *, group: str | None = None
               ) -> SegRequest:
        """Enqueue one (H, W, C) image; returns its request handle.
        ``group`` labels the request's tiles for group-scoped stepping
        (QoS classes at the gateway); ``None`` joins the unlabeled pool."""
        image = np.asarray(image)
        if (image.ndim != 3 or image.shape[-1] != self.cfg.in_ch
                or image.shape[0] < 1 or image.shape[1] < 1):
            raise ValueError(
                f"expected (H, W, {self.cfg.in_ch}) image with H, W >= 1, "
                f"got {image.shape}"
            )
        req = SegRequest(rid=self._next_rid, image=image, group=group)
        self._next_rid += 1
        self.queue.push(req)
        return req

    def _admit(self, req: SegRequest) -> bool:
        with _span("admit", rid=req.rid):
            # Plan before occupying: a planning error must not leak the slot.
            with _span("plan", rid=req.rid):
                req.plan = tiling.plan_tiles(
                    req.image.shape[0], req.image.shape[1],
                    depth=self.cfg.depth,
                    convs_per_stage=self.cfg.convs_per_stage, tile=self.tile,
                    halo=self.halo,
                )
            slot = self.slots.occupy(req)
            if slot is None:
                return False
            req.slot = slot
            with _span("canvas", rid=req.rid):
                canvas = tiling.pad_canvas(
                    req.image.astype(np.float32), req.plan)
                req.canvas_in = canvas
                req.canvas_out = np.zeros(
                    (req.plan.pad_h, req.plan.pad_w, self.cfg.n_classes),
                    np.float32,
                )
            req.remaining = req.plan.n_tiles
            req.ops = cm.model_ops(
                cm.unet_conv_layers(
                    (req.plan.pad_h, req.plan.pad_w), self.cfg.in_ch,
                    self.cfg.base, self.cfg.depth, self.cfg.convs_per_stage,
                )
            )
            with _span("classify", rid=req.rid):
                amax = float(np.max(np.abs(canvas)))
                if self.adaptive:
                    classes = adaptive.classify_tiles(
                        canvas, req.plan, max_class=self.max_class, amax=amax,
                        thresholds=(
                            None if self.plan is None
                            else self.plan.class_thresholds
                        ),
                    )
                else:
                    classes = [0] * req.plan.n_tiles
            # The octave key component keeps batch-shared dynamic scales
            # compatible; under a plan the forward quantizes per tile,
            # numerics are batch-composition independent, and splitting
            # groups by octave would only fragment the packing — so
            # collapse it.
            if self.plan is not None:
                octave = 0
            else:
                octave = int(math.floor(math.log2(amax))) if amax > 0 else 0
            for ti, (spec, k) in enumerate(zip(req.plan.tiles, classes)):
                key = (spec.in_h, spec.in_w, k, octave, req.group)
                self._tasks.setdefault(key, []).append((req, ti))
                req.class_counts[k] = req.class_counts.get(k, 0) + 1
            self.counters.admitted += 1
            return True

    # ------------------------------------------------------------- stepping

    def has_work(self, group: str | None = ...) -> bool:
        """Admitted tiles are waiting to run or, dispatched, to be
        collected (the public surface callers — the gateway's adapter —
        poll instead of reaching into the task table).  Pass ``group`` to
        ask about one scheduling group only (``...``, the default, means
        *any* group)."""
        return self.pending(group) > 0

    def pending(self, group: str | None = ...) -> int:
        """How many admitted tiles are waiting to run or to be collected."""
        def mine(key):
            return group is ... or key[4] == group

        return sum(len(g) for key, g in self._tasks.items() if mine(key)) + sum(
            len(taken) for key, taken, _ in self._inflight if mine(key)
        )

    def _next_key(self, group=...):
        keys = (
            list(self._tasks) if group is ...
            else [k for k in self._tasks if k[4] == group]
        )
        if not keys:
            return None
        if self.priority:
            return min(keys, key=lambda g: g[2])
        return keys[0]

    def next_cost(self, group: str | None = ...) -> int:
        """Relation-(2) price of the tiles the next :meth:`step` call with
        this ``group`` emits (0 when idle): the oldest in-flight
        micro-batch for an unscoped call, else the next one it launches;
        every in-flight micro-batch plus the group's next one for a scoped
        call.  The preemption point of the serving gateway: a step whose
        price exceeds the class's remaining quantum is not started — the
        quantum carries to the next round instead of the step overdrafting
        it."""
        batches = [(key, len(taken)) for key, taken, _ in self._inflight]
        if group is ... and batches:
            batches = batches[:1]
        else:
            key = self._next_key(group)
            if key is not None:
                batches.append((key, min(len(self._tasks[key]), self.batch)))
        return sum(n * self._tile_cycles(*key[:3]) for key, n in batches)

    def step(self, group: str | None = ...) -> list[TileEvent]:
        """Emit one micro-batch's tile events (empty when idle — falsy, so
        boolean call sites keep working).  ``group`` restricts the step to
        one scheduling group's tiles (the gateway's class-quantum
        accounting); the default serves any group.

        An unscoped call keeps one micro-batch dispatched ahead: it
        launches the next micro-batch (two when nothing was in flight),
        then blocks on the oldest one still on the device, stitches it and
        returns its events.  The device computes the next batch while the
        host fetches, stitches, admits and gathers, and every non-empty
        return holds exactly one micro-batch, at most ``batch`` tiles of
        one ``(in_h, in_w, klass)``; a batch is left in flight between
        calls.  A scoped call leaves nothing in flight: it first collects
        what unscoped calls left there and returns those events ahead of
        its own micro-batch's.

        Group choice is the prioritization point: structure-first (lowest
        budget class; FIFO among equals via dict insertion order) under
        ``priority=True``, plain admission order otherwise.  Only *which*
        group runs next changes — group membership and within-group batch
        packing are fixed at admission — so emission order is scheduling
        policy, not numerics (see the ``priority`` docstring for the one
        shared-scale caveat under slot churn, where packing a batch one
        step ahead can also move which requests share it).
        """
        if group is not ...:
            events: list[TileEvent] = []
            while self._inflight:
                key, taken, _ = self._inflight[0]
                with self._step_span(key, taken):
                    events += self._collect()
            own = self._take(group)
            if own is not None:
                with self._step_span(*own):
                    self._launch(*own)
                    events += self._collect()
            return events
        # pack one micro-batch ahead of the oldest in flight, two when
        # nothing is; the call emits the oldest
        launch = [self._take(group) for _ in range(1 if self._inflight else 2)]
        launch = [b for b in launch if b is not None]
        if not (self._inflight or launch):
            return []
        with self._step_span(*(self._inflight or launch)[0][:2]):
            for b in launch:
                self._launch(*b)
            return self._collect()

    @staticmethod
    def _step_span(key, taken):
        return _span("step", in_h=key[0], in_w=key[1], klass=key[2],
                     tiles=len(taken))

    def _take(self, group):
        """Pop the next micro-batch's tiles off the task table: ``(key,
        [(request, tile_index), ...])``, or None when none wait."""
        key = self._next_key(group)
        if key is None:
            return None
        task_group = self._tasks[key]
        self._tasks[key] = task_group[self.batch :]
        if not self._tasks[key]:
            del self._tasks[key]
        return key, task_group[: self.batch]

    def _launch(self, key, taken) -> None:
        """Gather, upload and dispatch one micro-batch, start its copy back
        to the host, and queue it for :meth:`_collect`."""
        in_h, in_w, k = key[0], key[1], key[2]
        with _span("gather"):
            x = np.zeros((self.batch, in_h, in_w, self.cfg.in_ch), np.float32)
            for b, (req, ti) in enumerate(taken):
                spec = req.plan.tiles[ti]
                x[b] = req.canvas_in[spec.y0 : spec.y1, spec.x0 : spec.x1]
        with _span("upload"):
            x_dev = jnp.asarray(x)
        with _span("dispatch"):
            out = self._fwd(self.params, x_dev, self.class_cfg(k))
            out.copy_to_host_async()  # starts as soon as the compute ends
            # release buffers inside a phase's span, so that the step's
            # own time stays bookkeeping (so ``del out`` in the stitch)
            del x_dev
            self.counters.launched_ahead += bool(self._inflight)
            self.counters.upload_bytes += x.nbytes
            self._inflight.append((key, taken, out))

    def _collect(self) -> list[TileEvent]:
        """Block on the oldest in-flight micro-batch, stitch it and return
        its tile events."""
        with _span("fetch"):
            key, taken, out = self._inflight.popleft()
            out = np.asarray(out)
        in_h, in_w, k = key[0], key[1], key[2]
        with _span("stitch"):
            c = self.counters
            c.steps += 1
            c.tiles += len(taken)
            c.tile_slots += self.batch
            c.window_pixels += len(taken) * in_h * in_w
            c.host_syncs += 1
            c.fetch_bytes += out.nbytes
            self._signatures.add((in_h, in_w, k))
            c.executables = len(self._signatures)
            events: list[TileEvent] = []
            cyc = self._tile_cycles(in_h, in_w, k)  # one price, both accounts
            pj = self._tile_pj(in_h, in_w, k)
            for b, (req, ti) in enumerate(taken):
                spec = req.plan.tiles[ti]
                cy, cx = spec.crop
                req.canvas_out[
                    spec.core_y0 : spec.core_y1, spec.core_x0 : spec.core_x1
                ] = out[b][cy, cx]
                req.cycles += cyc
                req.pj += pj
                req.remaining -= 1
                req.emitted.append(ti)
                if req.remaining == 0:
                    self._finish(req)
                events.append(
                    TileEvent(
                        rid=req.rid, tile=ti, klass=k, cycles=cyc,
                        core=(
                            spec.core_y0, spec.core_x0,
                            spec.core_y1, spec.core_x1,
                        ),
                        done=req.done, request=req, pj=pj,
                    )
                )
            del out
        return events

    def compiled_texts(self) -> dict[tuple[int, int, int], str]:
        """HLO text of the compiled tile forward of each (in_h, in_w, class)
        signature run so far.  Its ops' ``op_name`` metadata holds the
        forward's named scopes, which a TPU trace's op events lack.  Each
        is lowered and compiled again (JAX's compilation cache finds it
        where the cache is on)."""
        return {
            (h, w, k): self._fwd.lower(
                self.params,
                jax.ShapeDtypeStruct((self.batch, h, w, self.cfg.in_ch),
                                     jnp.float32),
                self.class_cfg(k),
            ).compile().as_text()
            for h, w, k in sorted(self._signatures)
        }

    def _finish(self, req: SegRequest) -> None:
        req.result = SegResult(
            logits=req.canvas_out[: req.plan.h, : req.plan.w].copy(),
            cycles=req.cycles,
            ops=req.ops,
            n_tiles=req.plan.n_tiles,
            class_counts=dict(sorted(req.class_counts.items())),
            pj=req.pj,
        )
        self.slots.release(req.slot)
        req.canvas_in = None
        req.canvas_out = None
        self.counters.completed += 1

    # ------------------------------------------------------------ the loop

    def run(self, images: list[np.ndarray]) -> list[SegResult]:
        """Serve a batch of images to completion, in submission order."""
        reqs = [self.submit(im) for im in images]
        self.flush()
        return [r.result for r in reqs]

    def flush(self) -> None:
        """Drain the queue and every in-flight request (the event-less
        view of :meth:`serve_stream` — one loop, two surfaces)."""
        for _ in self.serve_stream([]):
            pass

    def serve_stream(self, images: list[np.ndarray]):
        """Progressive serving: yield :class:`TileEvent` s as tiles finish.

        Under ``priority=True`` each image's structure-class tiles stream
        out before its background tiles; consume ``event.request.partial()``
        for the stitch so far and ``event.request.result`` once
        ``event.done``.  Equivalent to :meth:`run` in final outputs.

        Each round admits what the free slots allow, then runs one
        unscoped :meth:`step`: while the host fetches and stitches
        micro-batch n, admits, and gathers n + 2, the device computes
        n + 1.  The stream ends with nothing queued, admitted or in
        flight."""
        for im in images:
            self.submit(im)
        while self.queue or self.slots.any_active() or self.has_work():
            self.queue.pump(self.slots, self._admit)
            events = self.step()
            if not events and not self.queue:
                break
            yield from events
