"""U-Net (the paper's target application) with MMA-quantized 3x3 convs.

Faithful to the paper's deployment: the network is trained in float (or
QAT), quantized FBGEMM-style to int8, and its 3x3 convolutions execute on
the MSDF merged multiply-add datapath (``core.mma.mma_conv2d`` — the KPB
maps the k*k taps into the contraction dim).  2x2 pool/upsample
and the final 1x1 conv run off the accelerator, as in the paper (Sec. 3.1).

The default geometry is the Table-1-calibrated config
(``core.cycle_model.CALIBRATED_UNET``): 80x80x4 input, base 48, depth 3.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import mma, quant
from repro.core.cycle_model import CALIBRATED_UNET, ConvLayerSpec, unet_conv_layers
from repro.core.plane_schedule import PlaneSchedule


@dataclass(frozen=True)
class UNetConfig:
    hw: int = CALIBRATED_UNET["hw"]
    in_ch: int = CALIBRATED_UNET["in_ch"]
    base: int = CALIBRATED_UNET["base"]
    depth: int = CALIBRATED_UNET["depth"]
    convs_per_stage: int = CALIBRATED_UNET["convs_per_stage"]
    n_classes: int = 4
    quant_mode: str = "none"  # 'none' | 'mma_int8'
    planes: int = 8
    # Per-3x3-conv plane budgets, in forward order (enc, bottleneck, dec) —
    # same order as ``conv_layers()``.  None -> uniform ``planes``.
    plane_schedule: tuple[int, ...] | None = None
    impl: str = "xla"  # MMA datapath: 'xla' | 'pallas' (see core.mma)
    family: str = "unet"

    def conv_layers(self) -> list[ConvLayerSpec]:
        return unet_conv_layers(self.hw, self.in_ch, self.base, self.depth,
                                self.convs_per_stage)

    def schedule(self) -> PlaneSchedule:
        """The active per-layer precision policy (explicit or uniform)."""
        n = len(self.conv_layers())
        if self.plane_schedule is not None:
            if len(self.plane_schedule) != n:
                raise ValueError(
                    f"plane_schedule has {len(self.plane_schedule)} entries "
                    f"but this geometry (depth={self.depth}, "
                    f"convs_per_stage={self.convs_per_stage}) has {n} 3x3 "
                    f"convs — one budget per conv, in forward order"
                )
            return PlaneSchedule.from_list(self.plane_schedule)
        return PlaneSchedule.uniform(self.planes, n)

    # ------------------------------------------------------- tile geometry

    def min_viable_tile(self) -> int:
        """Smallest core stride worth tiling at: the first multiple of
        ``2**depth`` strictly larger than twice the receptive-field halo, so
        a tile's valid core is at least as large as the redundant context it
        pays for on each axis."""
        from repro.segserve.tiling import halo_for  # lazy: segserve imports us

        mult = 2**self.depth
        halo = halo_for(self.depth, self.convs_per_stage)
        return (2 * halo // mult + 1) * mult

    def validate_tile(self, tile: int, *, halo: int | None = None) -> int:
        """Geometry check for a tiled deployment of this net: rejects core
        strides the halo walk proves degenerate (``tile <= 2*halo`` means
        every interior window is mostly halo, so the tiling computes more
        redundant context than useful core) with the minimum viable tile
        named.  ``halo=None`` checks against the exact receptive-field halo;
        an explicit smaller halo (seam-tolerant modes) relaxes the check.
        Returns ``tile`` so call sites can validate inline."""
        from repro.segserve.tiling import halo_for  # lazy: segserve imports us

        mult = 2**self.depth
        if tile < mult or tile % mult:
            raise ValueError(
                f"tile {tile} must be a positive multiple of 2**depth = {mult}"
            )
        h = halo_for(self.depth, self.convs_per_stage) if halo is None else halo
        if h > 0 and tile <= 2 * h:
            min_viable = (2 * h // mult + 1) * mult
            raise ValueError(
                f"tile {tile} <= 2*halo = {2 * h} at depth {self.depth} "
                f"(convs_per_stage={self.convs_per_stage}): every interior "
                f"window would be mostly redundant halo context; the minimum "
                f"viable tile for this geometry is {min_viable}"
            )
        return tile


def _conv_init(key, kh, kw, cin, cout):
    std = 1.0 / jnp.sqrt(kh * kw * cin)
    return {
        "w": (jax.random.truncated_normal(key, -2, 2, (kh, kw, cin, cout), jnp.float32) * std),
        "b": jnp.zeros((cout,), jnp.float32),
    }


def init_params(key, cfg: UNetConfig) -> dict:
    keys = iter(jax.random.split(key, 64))
    p: dict = {"enc": [], "dec": []}
    ch = cfg.in_ch
    enc_ch = []
    for d in range(cfg.depth):
        c = cfg.base * (2**d)
        stage = [_conv_init(next(keys), 3, 3, ch, c)]
        for _ in range(cfg.convs_per_stage - 1):
            stage.append(_conv_init(next(keys), 3, 3, c, c))
        p["enc"].append(stage)
        enc_ch.append(c)
        ch = c
    c = cfg.base * (2**cfg.depth)
    p["bottleneck"] = [_conv_init(next(keys), 3, 3, ch, c)]
    for _ in range(cfg.convs_per_stage - 1):
        p["bottleneck"].append(_conv_init(next(keys), 3, 3, c, c))
    ch = c
    for d in reversed(range(cfg.depth)):
        c = enc_ch[d]
        stage = [_conv_init(next(keys), 3, 3, c + ch, c)]
        for _ in range(cfg.convs_per_stage - 1):
            stage.append(_conv_init(next(keys), 3, 3, c, c))
        p["dec"].append(stage)
        ch = c
    p["head"] = _conv_init(next(keys), 1, 1, ch, cfg.n_classes)
    return p


def conv3x3(p, x, cfg: UNetConfig, *, planes: int | None = None):
    """3x3 conv through the selected datapath (float or MMA int8).

    ``planes`` overrides the global ``cfg.planes`` for this layer — the hook
    the per-layer :class:`PlaneSchedule` drives.  Static per-layer budgets
    compile one specialized kernel variant per distinct count (shared across
    layers); on the TPU each is one int8 MXU pass on the truncated operand.
    """
    if planes is None:
        planes = cfg.planes
    if cfg.quant_mode == "mma_int8":
        with jax.named_scope("quant"):
            xq = quant.quantize_acts(x)
            wq = quant.quantize_weights(p["w"], channel_axis=-1)
        out = mma.mma_conv2d(xq.values, wq.values, planes=planes, impl=cfg.impl)
        with jax.named_scope("rescale"):
            out = out.astype(jnp.float32) * quant.quantized_matmul_scale(xq.scale, wq.scale)
            return out + p["b"]
    out = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    return out + p["b"]


def forward(params, x, cfg: UNetConfig, *, planes_arr=None, taps=None):
    """x: (N, H, W, Cin) -> logits (N, H, W, n_classes).

    3x3 convs are visited in the same order as ``cfg.conv_layers()`` /
    ``unet_conv_layers`` (encoder, bottleneck, decoder), so schedule entry
    ``l`` lines up with cycle-model layer ``l``.

    Spatial dims need not equal ``cfg.hw`` (halo tiles of the segmentation
    server run rectangular crops through this same function), but both must
    divide by ``2**depth`` so the pool/upsample ladder round-trips; anything
    else used to die deep in the decoder concat, so reject it up front.

    Two calibration hooks (``repro.autotune``), both off by default:

    ``planes_arr``
        an (L,) int32 array of per-conv plane budgets that *overrides*
        ``cfg``'s schedule.  Because it may be a traced value (the budgets
        ride in as data via the exact bit-mask identity,
        ``bitplane.truncate_to_planes``), one compilation serves every
        candidate schedule — the search loop sweeps hundreds of schedules
        without retracing.  Quantized datapath only; ignored for float.
    ``taps``
        a list to append each post-ReLU conv activation to, in schedule
        order — the instrumented forward activation statistics are read
        from.  Appends traced arrays under ``jit``; have the jitted wrapper
        return them.
    """
    mult = 2**cfg.depth
    if x.shape[1] % mult or x.shape[2] % mult:
        raise ValueError(
            f"spatial dims {x.shape[1]}x{x.shape[2]} not divisible by "
            f"2**depth = {mult}; pad the input (segserve.tiling.plan_tiles "
            f"does this for arbitrary images)"
        )
    sched = cfg.schedule() if cfg.quant_mode == "mma_int8" else None
    li = 0

    def qconv(conv, h):
        nonlocal li
        if planes_arr is not None and cfg.quant_mode == "mma_int8":
            pl = planes_arr[li]
        else:
            pl = sched.planes_for(li) if sched is not None else None
        with jax.named_scope(f"conv{li:02d}"):
            out = jax.nn.relu(conv3x3(conv, h, cfg, planes=pl))
        li += 1
        if taps is not None:
            taps.append(out)
        return out

    skips = []
    h = x
    for stage in params["enc"]:
        for conv in stage:
            h = qconv(conv, h)
        skips.append(h)
        with jax.named_scope("pool"):
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    for conv in params["bottleneck"]:
        h = qconv(conv, h)
    for d, stage in enumerate(params["dec"]):
        # 2x nearest upsample (off-accelerator op, like the paper's 2x2 path)
        with jax.named_scope("upsample"):
            n, hh, ww, c = h.shape
            h = jnp.broadcast_to(h[:, :, None, :, None, :], (n, hh, 2, ww, 2, c)).reshape(
                n, hh * 2, ww * 2, c
            )
            h = jnp.concatenate([skips[-(d + 1)], h], axis=-1)
        for conv in stage:
            h = qconv(conv, h)
    with jax.named_scope("head"):
        out = jax.lax.conv_general_dilated(
            h, params["head"]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return out + params["head"]["b"]


def forward_with_error_bound(params, x, cfg: UNetConfig):
    """Scheduled forward plus a *sound* end-to-end error certificate.

    Returns ``(out_sched, out_full, advertised_rel_bound)`` where
    ``out_sched`` is the forward under ``cfg``'s plane schedule, ``out_full``
    the same datapath at full 8-plane precision, and the bound satisfies

        max|out_sched - out_full|  <=  advertised_rel_bound * max|out_full|

    by construction.  The certificate is interval propagation through the
    exact forward graph: each truncated conv contributes its analytic
    worst-case truncation error ((2^d - 1) * colsum|w_q|, ``early_term``)
    plus the activation-requantization jitter of both paths, and upstream
    error is amplified by the layer's L-inf operator norm (max column L1 of
    the dequantized weight).  ReLU / maxpool / 2x-upsample are 1-Lipschitz
    and concat takes the max of branch errors, so the composition is
    worst-case sound — unlike the first-order per-layer sum
    (``PlaneSchedule.rel_err_bound``), which ignores inter-layer gain.
    """
    from repro.core.bitplane import N_BITS

    sched = cfg.schedule()
    full_cfg = dataclasses.replace(cfg, plane_schedule=None, planes=8)
    out_full = forward(params, x, full_cfg)
    out_sched = forward(params, x, cfg)

    # --- interval propagation along the same graph -------------------------
    li = 0
    err = 0.0  # abs L-inf bound on (sched activation - full activation)

    def conv_err(p, h_ref, err_in):
        nonlocal li
        planes = sched.planes_for(li)
        li += 1
        wq = quant.quantize_weights(p["w"], channel_axis=-1)
        w2 = wq.values.reshape(-1, wq.values.shape[-1]).astype(jnp.int32)
        ws = jnp.squeeze(wq.scale)  # (cout,)
        # dequantized per-column L1 — the L-inf operator norm of the conv
        col_l1 = jnp.sum(jnp.abs(w2), axis=0).astype(jnp.float32) * ws
        opnorm = float(jnp.max(col_l1))
        amax_ref = float(jnp.max(jnp.abs(h_ref)))
        s_ref = max(amax_ref, 1e-8) / 127.0
        s_sched = max(amax_ref + err_in, 1e-8) / 127.0
        dropped = N_BITS - planes
        if err_in == 0.0 and dropped == 0:
            return 0.0  # identical datapaths
        # input divergence + the two paths' requantization jitter
        din = err_in + 0.5 * (s_ref + s_sched)
        e = opnorm * din
        if dropped:
            # truncation of the scheduled path's planes, in float units:
            # (2^d - 1) * max col-L1 of the dequantized weight * act scale
            e += (2**dropped - 1) * opnorm * s_sched
        return e

    # replay the forward structure on the *reference* activations
    h = x
    skips = []
    skip_errs = []
    for stage in params["enc"]:
        for conv in stage:
            err = conv_err(conv, h, err)
            h = jax.nn.relu(conv3x3(conv, h, full_cfg))
        skips.append(h)
        skip_errs.append(err)
        h = jax.lax.reduce_window(
            h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
        )
    for conv in params["bottleneck"]:
        err = conv_err(conv, h, err)
        h = jax.nn.relu(conv3x3(conv, h, full_cfg))
    for d, stage in enumerate(params["dec"]):
        n, hh, ww, c = h.shape
        h = jnp.broadcast_to(h[:, :, None, :, None, :], (n, hh, 2, ww, 2, c)).reshape(
            n, hh * 2, ww * 2, c
        )
        h = jnp.concatenate([skips[-(d + 1)], h], axis=-1)
        err = max(err, skip_errs[-(d + 1)])
        for conv in stage:
            err = conv_err(conv, h, err)
            h = jax.nn.relu(conv3x3(conv, h, full_cfg))
    # float 1x1 head, shared by both paths: pure propagation
    w_head = params["head"]["w"].reshape(-1, params["head"]["w"].shape[-1])
    err = err * float(jnp.max(jnp.sum(jnp.abs(w_head), axis=0)))

    denom = max(float(jnp.max(jnp.abs(out_full))), 1e-8)
    return out_sched, out_full, err / denom


def conv_weights_in_order(params) -> list[jax.Array]:
    """Float 3x3-conv weights in forward order (enc, bottleneck, dec)."""
    ws = []
    for stage in params["enc"]:
        ws += [conv["w"] for conv in stage]
    ws += [conv["w"] for conv in params["bottleneck"]]
    for stage in params["dec"]:
        ws += [conv["w"] for conv in stage]
    return ws


def schedule_from_params(
    params, target_rel_err: float
) -> PlaneSchedule:
    """Build the per-layer precision policy from this net's actual weights:
    quantize each 3x3 conv FBGEMM-style and pick the fewest planes whose
    analytic worst-case relative error meets ``target_rel_err``."""
    wq = [
        quant.quantize_weights(w, channel_axis=-1).values.reshape(-1, w.shape[-1])
        for w in conv_weights_in_order(params)
    ]
    return PlaneSchedule.from_weights(wq, target_rel_err)


def loss_fn(params, batch, cfg: UNetConfig):
    """Segmentation cross-entropy; batch = {"image": (N,H,W,C), "mask": (N,H,W)}."""
    logits = forward(params, batch["image"], cfg).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["mask"][..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    return nll, {"nll": nll}
