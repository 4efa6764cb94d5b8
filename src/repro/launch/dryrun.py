import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# NOTE: the two lines above MUST run before any jax import (jax locks the
# device count at first init). Do not move them.

"""Multi-pod dry-run: AOT lower + compile every (arch x input-shape x mesh)
combination on the production mesh, and extract the roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all            # 40 baselines
  PYTHONPATH=src python -m repro.launch.dryrun --all --multipod # 512-chip pass

Each run appends a JSON record to --out (default benchmarks/dryrun_results.json):
bytes-per-device, HLO FLOPs, HLO bytes accessed, per-collective byte counts
parsed from the compiled HLO, compile wall time, and the analytic model
FLOPs — everything EXPERIMENTS.md §Dry-run / §Roofline reads.
"""
import argparse
import json
import re
import time
from typing import Any, Dict, Optional

import jax

from repro.configs.base import INPUT_SHAPES, ArchConfig, InputShape, get_arch
from repro.launch import sharding as shd
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.launch.steps import (make_decode_step, make_prefill_step,
                                make_train_step, train_state_shapes)
from repro.models.registry import build_model
from repro.optim import adamw

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32"
    r"|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op in the HLO module."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.lstrip()
        # HLO: `%name = TYPE[SHAPE] all-gather(...)` or fusion-wrapped
        m = None
        for c in _COLLECTIVES:
            if (f" {c}(" in stripped or f"={c}(" in stripped
                    or stripped.startswith(c + "(")):
                m = c
                break
            if f" {c}-start(" in stripped or f" {c}-done(" in stripped:
                m = c if "-start(" in stripped else None
                break
        if m is None:
            continue
        # take the shapes on the rhs — for tuples, sum all
        rhs = stripped.split("=", 1)[1] if "=" in stripped else stripped
        # result shape(s) appear at start of rhs before the op name
        op_idx = rhs.find(m)
        result_part = rhs[:op_idx] if op_idx > 0 else rhs
        total = 0
        for dt, dims in _SHAPE_RE.findall(result_part):
            total += _shape_bytes(dt, dims)
        if total == 0:  # fall back: any shape on the line
            for dt, dims in _SHAPE_RE.findall(stripped):
                total += _shape_bytes(dt, dims)
                break
        out[m] += total
        counts[m] += 1
    out_all = dict(out)
    out_all["counts"] = counts
    return out_all


# ---------------------------------------------------------------------------
# model FLOPs (analytic)
# ---------------------------------------------------------------------------


def model_flops(cfg: ArchConfig, shape: InputShape, n_params: int,
                n_active: Optional[int] = None) -> float:
    """6·N·D for training, 2·N·D for inference (N = active params)."""
    n = n_active if (n_active and cfg.n_experts) else n_params
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one token per sequence
    return 2.0 * n * tokens


def active_params(cfg: ArchConfig, n_params: int) -> int:
    """Rough active-parameter count for MoE (top-k of E experts)."""
    if not cfg.n_experts:
        return n_params
    F = cfg.moe_d_ff or cfg.d_ff
    expert_params = cfg.n_layers * cfg.n_experts * 3 * cfg.d_model * F
    active_expert = expert_params * cfg.experts_per_token / cfg.n_experts
    return int(n_params - expert_params + active_expert)


# ---------------------------------------------------------------------------
# dry-run core
# ---------------------------------------------------------------------------


def _lower_combo(cfg: ArchConfig, shape: InputShape, mesh) -> Any:
    """Build the jitted step for (cfg, shape) and AOT-lower it."""
    model = build_model(cfg)
    if shape.kind == "train":
        opt = adamw(1e-4)
        state_shapes = train_state_shapes(model, opt)
        batch_shapes = model.input_spec(shape)
        # optimizer state mirrors the params' sharding (ZeRO for free)
        state_specs = {
            "params": shd.tree_param_specs(state_shapes["params"], mesh,
                                           n_kv_heads=cfg.n_kv_heads),
            "opt": {k: shd.tree_param_specs(v, mesh,
                                            n_kv_heads=cfg.n_kv_heads)
                    for k, v in state_shapes["opt"].items()},
            "step": jax.sharding.PartitionSpec(),
        }
        batch_specs = shd.batch_spec(batch_shapes, mesh)
        jitted = jax.jit(
            make_train_step(model, opt),
            in_shardings=(shd.to_named(state_specs, mesh),
                          shd.to_named(batch_specs, mesh)),
            donate_argnums=(0,),
        )
        return jitted.lower(state_shapes, batch_shapes), {}
    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    param_specs = shd.tree_param_specs(params_shapes, mesh,
                                       n_kv_heads=cfg.n_kv_heads)
    batch_shapes = model.input_spec(shape)
    batch_specs = shd.batch_spec(batch_shapes, mesh)
    if shape.kind == "prefill":
        jitted = jax.jit(
            make_prefill_step(model),
            in_shardings=(shd.to_named(param_specs, mesh),
                          shd.to_named(batch_specs, mesh)),
        )
        return jitted.lower(params_shapes, batch_shapes), {}
    # decode
    cache_len = model.cache_len_for(shape.seq_len)
    window = model.decode_window_for(shape.seq_len)
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, cache_len))
    cache_specs = shd.cache_spec(cache_shapes, mesh)
    jitted = jax.jit(
        make_decode_step(model, window=window),
        in_shardings=(shd.to_named(param_specs, mesh),
                      shd.to_named(cache_specs, mesh),
                      shd.to_named(batch_specs, mesh)),
        donate_argnums=(1,),
    )
    lowered = jitted.lower(params_shapes, cache_shapes, batch_shapes)
    return lowered, {"cache_len": cache_len, "window": window}


def _compile_costs(lowered) -> Dict[str, Any]:
    """Compile and pull flops/bytes/collectives out of the artifact."""
    t0 = time.time()
    compiled = lowered.compile()
    out: Dict[str, Any] = {"compile_s": round(time.time() - t0, 2)}
    mem = compiled.memory_analysis()
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        out[attr] = getattr(mem, attr, None)
    cost = compiled.cost_analysis() or {}
    out["flops"] = cost.get("flops", 0.0)
    out["bytes_accessed"] = cost.get("bytes accessed", 0.0)
    hlo = compiled.as_text()
    out["collectives"] = collective_bytes(hlo)
    out["hlo_len"] = len(hlo)
    return out


def _calib_cfgs(cfg: ArchConfig):
    """1-unit and 2-unit unrolled variants + the unit count for extrapolation."""
    base = dict(unroll_layers=True, unroll_attn=True, attn_chunk=4096,
                loss_chunk=1 << 30)
    if cfg.family == "hybrid":
        e = cfg.attn_every
        units = cfg.n_layers // e
        return (cfg.with_(n_layers=e, **base),
                cfg.with_(n_layers=2 * e, **base), units)
    if cfg.family == "audio":
        return (cfg.with_(n_layers=1, encoder_layers=1, **base),
                cfg.with_(n_layers=2, encoder_layers=2, **base),
                cfg.n_layers)
    return (cfg.with_(n_layers=1, **base),
            cfg.with_(n_layers=2, **base), cfg.n_layers)


def _extrapolate(c1: Dict[str, Any], c2: Dict[str, Any], units: int) -> Dict[str, Any]:
    """True-depth cost estimate: C(L) = C(1) + (L-1) * (C(2) - C(1))."""
    out: Dict[str, Any] = {}
    for k in ("flops", "bytes_accessed"):
        per_unit = (c2[k] or 0) - (c1[k] or 0)
        out[k] = (c1[k] or 0) + (units - 1) * per_unit
    coll: Dict[str, Any] = {}
    for name in _COLLECTIVES:
        per_unit = c2["collectives"][name] - c1["collectives"][name]
        coll[name] = c1["collectives"][name] + (units - 1) * per_unit
    coll["counts"] = {
        name: c1["collectives"]["counts"][name]
        + (units - 1) * (c2["collectives"]["counts"][name]
                         - c1["collectives"]["counts"][name])
        for name in _COLLECTIVES}
    out["collectives"] = coll
    return out


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               calibrate: bool = True) -> Dict[str, Any]:
    cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "multi_pod": multi_pod,
    }

    with jax.set_mesh(mesh):
        try:
            # ---- the deliverable: full production config lowers + compiles
            t0 = time.time()
            lowered, extra = _lower_combo(cfg, shape, mesh)
            record.update(extra)
            record["lower_s"] = round(time.time() - t0, 2)
            main = _compile_costs(lowered)
            record.update(main)
            record["status"] = "ok"

            # ---- analytic reference
            n_params = sum(x.size for x in jax.tree.leaves(
                jax.eval_shape(model.init, jax.random.key(0))))
            n_act = active_params(cfg, n_params)
            record["n_params"] = int(n_params)
            record["n_active_params"] = int(n_act)
            record["model_flops"] = model_flops(cfg, shape, n_params, n_act)

            # ---- cost calibration: scans hide per-layer cost from XLA's
            # analysis, so extrapolate true depth from unrolled 1/2-unit runs.
            flops = main["flops"] or 0.0
            byts = main["bytes_accessed"] or 0.0
            coll = main["collectives"]
            if calibrate:
                try:
                    cfg1, cfg2, units = _calib_cfgs(cfg)
                    l1, _ = _lower_combo(cfg1, shape, mesh)
                    c1 = _compile_costs(l1)
                    l2, _ = _lower_combo(cfg2, shape, mesh)
                    c2 = _compile_costs(l2)
                    ext = _extrapolate(c1, c2, units)
                    record["calibrated"] = True
                    record["calib_units"] = units
                    record["calib_compile_s"] = c1["compile_s"] + c2["compile_s"]
                    flops = ext["flops"]
                    byts = ext["bytes_accessed"]
                    coll = ext["collectives"]
                    record["flops_extrap"] = flops
                    record["bytes_extrap"] = byts
                    record["collectives_extrap"] = coll
                except Exception as e:  # noqa: BLE001
                    record["calibrated"] = False
                    record["calib_error"] = f"{type(e).__name__}: {e}"[:300]

            coll_total = sum(v for k, v in coll.items() if k != "counts")
            record["collective_bytes_total"] = coll_total
            # cost_analysis FLOPs/bytes are per-device-program (SPMD), i.e.
            # one chip's slice — roofline terms are per chip directly.
            record["t_compute_s"] = flops / PEAK_FLOPS_BF16
            record["t_memory_s"] = byts / HBM_BW
            record["t_collective_s"] = coll_total / ICI_BW
            terms = {"compute": record["t_compute_s"],
                     "memory": record["t_memory_s"],
                     "collective": record["t_collective_s"]}
            record["bottleneck"] = max(terms, key=terms.get)
            return record
        except Exception as e:  # noqa: BLE001 — we want the failure in the table
            record["status"] = "error"
            record["error"] = f"{type(e).__name__}: {e}"[:500]
            return record


LONG_SKIP: Dict[str, str] = {}  # all archs lower for long_500k (window cache)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--no-calib", action="store_true",
                    help="skip the unrolled cost-calibration lowerings")
    ap.add_argument("--out", default="benchmarks/dryrun_results.json")
    args = ap.parse_args()

    from repro.configs.all_archs import ASSIGNED_ARCHS

    combos = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        combos.append((args.arch, args.shape))

    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r.get("multi_pod", False))
            for r in results if r.get("status") == "ok"}

    for arch, shape in combos:
        key = (arch, shape, args.multipod)
        if key in done:
            print(f"[skip] {arch} x {shape} (cached)")
            continue
        print(f"[dryrun] {arch} x {shape} multi_pod={args.multipod} ...",
              flush=True)
        rec = dryrun_one(arch, shape, multi_pod=args.multipod,
                         calibrate=not args.no_calib)
        print(f"  -> {rec['status']}"
              + (f" compile={rec.get('compile_s')}s"
                 f" flops={rec.get('flops'):.3g}"
                 f" bottleneck={rec.get('bottleneck')}"
                 if rec["status"] == "ok" else f" {rec.get('error','')[:200]}"),
              flush=True)
        results = [r for r in results
                   if not (r["arch"] == arch and r["shape"] == shape
                           and r.get("multi_pod", False) == args.multipod)]
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
