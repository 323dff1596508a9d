import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
#
# Multi-pod dry-run: lower + compile every (arch x shape) on the production
# meshes, record memory_analysis / cost_analysis / collective schedule.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch yi_6b --shape train_4k
#     PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
#
# Outputs one JSON per cell under results/dryrun/ (read by benchmarks/roofline
# and EXPERIMENTS.md).  A cell FAILING to compile is a bug in the framework's
# sharding config — the point of this deliverable.

import argparse
import json
import time
import traceback
from pathlib import Path


from repro.configs import ARCH_IDS, get_config
from repro.configs.base import cells
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _cell_costs(cfg, shape_name, mesh) -> dict:
    """Compile one config and return raw cost numbers (per-device module)."""
    cell = specs.build_cell(cfg, shape_name, mesh)
    with mesh:
        compiled = cell["fn"].lower(*cell["args"]).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    cost = cost or {}
    coll = hlo_analysis.collective_stats(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total_bytes"]),
        "coll_count": float(coll["total_count"]),
    }


def probe_costs(cfg, shape_name: str, mesh) -> dict:
    """True per-step costs via small UNROLLED probe compiles + linear
    extrapolation in depth (XLA cost_analysis counts a scan body once
    regardless of trip count — verified; see EXPERIMENTS.md §Dry-run).

    dense/moe/vlm/ssm:  v(L) = a + b*L, probes L=1,2 -> v(L_full)
    encdec:             enc_layers = n_layers = L probes (joint body)
    hybrid (zamba2):    probes at {g, 2g, g+tail}: v = v_g
                        + (v_2g - v_g)*(n_groups-1) + (v_{g+tail} - v_g)
    The microbatch loop is removed for probes (flops are mb-invariant; the
    grad sync happens once either way).
    """
    base = dict(microbatches=1, scan_unroll=True)
    fam = cfg.family
    if fam == "hybrid":
        g = cfg.attn_every or 6
        n_groups = cfg.n_layers // g
        tail = cfg.n_layers - n_groups * g
        v_g = _cell_costs(cfg.replace(n_layers=g, **base), shape_name, mesh)
        v_2g = _cell_costs(cfg.replace(n_layers=2 * g, **base), shape_name, mesh)
        out = {}
        if tail:
            v_gt = _cell_costs(cfg.replace(n_layers=g + tail, **base), shape_name, mesh)
        for k in v_g:
            full = v_g[k] + (v_2g[k] - v_g[k]) * (n_groups - 1)
            if tail:
                full += v_gt[k] - v_g[k]
            out[k] = full
        return out
    if fam == "encdec":
        v1 = _cell_costs(cfg.replace(n_layers=1, enc_layers=1, **base), shape_name, mesh)
        v2 = _cell_costs(cfg.replace(n_layers=2, enc_layers=2, **base), shape_name, mesh)
        return {k: v1[k] + (v2[k] - v1[k]) * (cfg.n_layers - 1) for k in v1}
    v1 = _cell_costs(cfg.replace(n_layers=1, **base), shape_name, mesh)
    v2 = _cell_costs(cfg.replace(n_layers=2, **base), shape_name, mesh)
    return {k: v1[k] + (v2[k] - v1[k]) * (cfg.n_layers - 1) for k in v1}


def apply_overrides(cfg, overrides: dict):
    """Apply dotted-key overrides, e.g. {'moe.ep': True, 'attn_chunk': 512}."""
    import dataclasses as dc

    plain = {k: v for k, v in overrides.items() if "." not in k}
    nested: dict[str, dict] = {}
    for k, v in overrides.items():
        if "." in k:
            outer, inner = k.split(".", 1)
            nested.setdefault(outer, {})[inner] = v
    if plain:
        cfg = cfg.replace(**plain)
    for outer, kv in nested.items():
        cfg = cfg.replace(**{outer: dc.replace(getattr(cfg, outer), **kv)})
    return cfg


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, quant: str = "none",
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if quant != "none":
        from repro.configs.base import QuantConfig

        # serving deploy mode: pre-quantized int8 weights + int8 KV cache,
        # the int8 MXU dot as the compute model (the Pallas kernel is the
        # TPU implementation; it makes the same one int8 pass per block).
        cfg = cfg.replace(quant=QuantConfig(
            mode=quant, impl="xla", weights_int8=True, kv_int8=True))
    if overrides:
        cfg = apply_overrides(cfg, overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = specs.build_cell(cfg, shape_name, mesh)
    with mesh:
        lowered = cell["fn"].lower(*cell["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_d = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            mem_d[k] = int(v)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    cost_d = {k: float(v) for k, v in (cost or {}).items()
              if isinstance(v, (int, float)) and (
                  k in ("flops", "bytes accessed") or k.startswith("bytes accessed"))}

    hlo = compiled.as_text()
    coll = hlo_analysis.collective_stats(hlo)
    census = hlo_analysis.remat_census(hlo)

    # True per-step costs (scan bodies are cost-counted once; extrapolate
    # from small unrolled probes).
    t1 = time.time()
    corrected = probe_costs(cfg, shape_name, mesh)
    t_probe = time.time() - t1
    flops = corrected["flops"]
    coll_bytes = corrected["coll_bytes"]
    # Memory term: analytic HBM traffic model (cost_analysis bytes ignore
    # fusion — kept as "bytes_upper_bound"); see hlo_analysis docstring.
    mem_model = hlo_analysis.analytic_hbm_bytes(cell["kind"], **cell["meta"]["mem_in"])
    roof = hlo_analysis.roofline(flops, mem_model["total"], coll_bytes)

    n_chips = mesh.devices.size
    meta = cell["meta"]
    # MODEL_FLOPS: 6*N*D train (fwd+bwd), 2*N*D inference, per device.
    n_active = meta["active_params"]
    d_tokens = meta["tokens"]
    mult = 6 if cell["kind"] == "train" else 2
    model_flops_global = mult * n_active * d_tokens
    model_flops_per_chip = model_flops_global / n_chips
    useful = model_flops_per_chip / flops if flops else 0.0

    out = dict(
        arch=arch, shape=shape_name, kind=cell["kind"],
        mesh="2x16x16" if multi_pod else "16x16", chips=int(n_chips),
        quant=quant,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        probe_s=round(t_probe, 2),
        params=meta["params"], active_params=n_active,
        serve_mode=meta.get("serve_mode", "-"),
        memory=mem_d, cost_raw=cost_d, cost=corrected,
        hbm_traffic_model=mem_model,
        collectives=coll, census=census,
        roofline=roof,
        model_flops_per_chip=model_flops_per_chip,
        useful_flops_fraction=useful,
    )
    return out


def save(result: dict, tag: str = "") -> Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    name = f"{result['arch']}__{result['shape']}__{result['mesh'].replace('x','_')}"
    if result.get("quant", "none") != "none":
        name += f"__{result['quant']}"
    if tag:
        name += f"__{tag}"
    p = RESULTS / f"{name}.json"
    p.write_text(json.dumps(result, indent=1))
    return p


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--quant", default="none")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (dotted keys ok), e.g. "
                         "--set moe.ep=True --set microbatches=8")
    args = ap.parse_args()

    import ast

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    todo: list[tuple[str, str]] = []
    if args.all:
        for a in ARCH_IDS:
            for s in cells(a):
                todo.append((a, s))
    else:
        assert args.arch and args.shape
        todo = [(args.arch, args.shape)]

    meshes = []
    if args.multi_pod or not args.single_pod:
        meshes.append(True)
    if args.single_pod or not args.multi_pod:
        meshes.insert(0, False)

    failures = 0
    for arch, shape in todo:
        for mp in meshes:
            mesh_tag = "2_16_16" if mp else "16_16"
            out_name = f"{arch}__{shape}__{mesh_tag}"
            if args.quant != "none":
                out_name += f"__{args.quant}"
            if args.tag:
                out_name += f"__{args.tag}"
            if args.skip_existing and (RESULTS / f"{out_name}.json").exists():
                print(f"[skip] {out_name}")
                continue
            try:
                r = run_cell(arch, shape, multi_pod=mp, quant=args.quant,
                             overrides=overrides or None)
                save(r, args.tag)
                roof = r["roofline"]
                print(
                    f"[ok] {out_name}: compile {r['compile_s']:.1f}s+{r['probe_s']:.1f}s "
                    f"flops/chip {r['cost']['flops']:.3e} "
                    f"coll {r['cost']['coll_bytes']:.3e}B "
                    f"dominant={roof['dominant']} "
                    f"bound={roof['step_time_lower_bound_s']*1e3:.2f}ms "
                    f"useful={r['useful_flops_fraction']:.2f}",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                print(f"[FAIL] {out_name}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
