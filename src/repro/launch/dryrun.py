import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on
the production meshes and extract the roofline terms.

For each cell:
    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...,
                           donate_argnums=...).lower(*ShapeDtypeStructs)
        compiled = lowered.compile()
        memory_analysis / cost_analysis / HLO collective scan

Success of compile() for the 16x16 (single-pod) and 2x16x16 (multi-pod)
meshes is deliverable (e); the JSON artifacts written to
``experiments/dryrun/`` feed the roofline table (EXPERIMENTS.md §Roofline)
and the perf loop (§Perf).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import SHAPES, get_config, list_archs, shapes_for
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import lower_cell, plan_cell

# ----------------------------------------------------- hardware constants --
PEAK_FLOPS = 197e12          # bf16 per chip (TPU v5e)
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link (DCI noted in DESIGN.md)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one 'f32[16,1024]'-style result (tuples: sum members)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum result-operand sizes of every collective op in the HLO.

    Returns (total_bytes, by_op dict).  The result shape of a collective
    equals (or bounds) its wire payload per device: all-reduce result ==
    contribution, all-gather result == gathered payload received,
    reduce-scatter result == the reduced shard, all-to-all == exchanged.
    """
    by_op = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        by_op[op] = by_op.get(op, 0) + b
    return sum(by_op.values()), by_op


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS for the cell (6ND train / 2ND per decode token,
    N = active *matmul* params for MoE) + attention score/value flops.

    The input-embedding table is a gather (0 flops), so it is excluded;
    for tied embeddings the table still does the head matmul and counts
    once (param_count already holds it once in that case).
    """
    n_active = cfg.active_param_count()
    if not cfg.tie_embeddings:
        n_active -= cfg.vocab_size * cfg.d_model   # gather-only input embed
    B, S = shape.global_batch, shape.seq_len

    # attention layer count + per-token context length: hybrids attend on
    # a fraction of layers with a bounded window (recurrentgemma: 1/3 of
    # layers, 2048-window), so full-S^2 accounting badly over-counts.
    n_att = 0 if cfg.attn_free else cfg.num_layers
    ctx_full = S
    if cfg.hybrid and cfg.block_pattern:
        frac = cfg.block_pattern.count("local") / len(cfg.block_pattern)
        n_att = cfg.num_layers * frac
        ctx_full = min(S, cfg.local_window or S)

    def att_flops(tokens_per_row, causal_half):
        ctx = ctx_full if not causal_half else ctx_full / 2 \
            if ctx_full == S else ctx_full  # windowed causal ~= window
        return n_att * B * 2 * 2 * tokens_per_row * ctx * cfg.q_dim

    if shape.kind == "train":
        flops = 6.0 * n_active * B * S
        if n_att and cfg.num_heads:
            flops += 3.0 * att_flops(S, causal_half=True)   # fwd + 2x bwd
        return flops
    if shape.kind == "prefill":
        flops = 2.0 * n_active * B * S
        if n_att and cfg.num_heads:
            flops += att_flops(S, causal_half=True)
        return flops
    # decode: one token against an S-long (or window-bounded) cache
    flops = 2.0 * n_active * B
    if n_att and cfg.num_heads:
        flops += att_flops(1, causal_half=False)
    return flops


def exec_flops(cfg, shape) -> float:
    """FLOPs the compiled step actually executes (analytic): MODEL_FLOPS
    plus the remat recompute (one extra forward per layer for train).

    XLA's HloCostAnalysis counts every while-loop *body once* (scan trip
    counts are not folded in), so ``cost_analysis()['flops']`` badly
    undercounts scanned-layers programs; the roofline compute term uses
    this analytic count instead (validated against an unrolled-HLO audit
    in tests/test_dryrun_audit.py).
    """
    mf = model_flops(cfg, shape)
    if shape.kind == "train" and cfg.remat:
        return mf * 8.0 / 6.0       # fwd + recomputed fwd + 2x bwd
    return mf


def analyze(compiled, lowered, cfg, shape, mesh) -> dict:
    n_dev = mesh.devices.size
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware HLO accounting (launch.hlo_cost) — XLA's builtin
    # counts while bodies once, useless for scanned-layers programs
    acc = analyze_hlo(hlo)
    flops = acc["flops"]
    bytes_accessed = acc["bytes"]
    coll_bytes = acc["collective_bytes"]
    by_op = {k: int(v) for k, v in acc["collectives_by_op"].items()}

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception:
        pass

    mf = model_flops(cfg, shape)
    ef = exec_flops(cfg, shape)                 # analytic cross-check
    compute_term = flops / PEAK_FLOPS
    memory_term = bytes_accessed / HBM_BW
    collective_term = coll_bytes / LINK_BW
    dominant = max(
        (("compute", compute_term), ("memory", memory_term),
         ("collective", collective_term)), key=lambda kv: kv[1])[0]
    return {
        "arch": cfg.name, "shape": shape.name, "kind": shape.kind,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "devices": int(n_dev),
        "hlo_flops_per_dev": flops,             # trip-count corrected
        "hlo_bytes_per_dev": bytes_accessed,
        "collective_bytes_per_dev": float(coll_bytes),
        "collectives_by_op": by_op,
        "xla_static_flops": float(cost.get("flops", 0.0)),
        "compute_term_s": compute_term,
        "memory_term_s": memory_term,
        "collective_term_s": collective_term,
        "dominant": dominant,
        "model_flops_global": mf,
        "model_flops_per_dev": mf / n_dev,
        "exec_flops_analytic_per_dev": ef / n_dev,
        "useful_flops_ratio": (mf / n_dev) / flops if flops else 0.0,
        "memory": mem,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             icq_grad: bool = False, attn_impl: str = "chunked",
             out_dir: str = "experiments/dryrun", verbose: bool = True,
             variant: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    if variant == "icq_kv":
        from repro.launch.steps import plan_icq_kv_cell
        plan = plan_icq_kv_cell(cfg, shape, mesh)
    else:
        plan = plan_cell(cfg, shape, mesh, icq_grad=icq_grad,
                         attn_impl=attn_impl)
    lowered = lower_cell(plan)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    rec = analyze(compiled, lowered, plan.cfg, shape, mesh)
    rec.update(n_micro=plan.n_micro, lower_s=round(t_lower, 1),
               compile_s=round(t_compile, 1), icq_grad=icq_grad,
               attn_impl=attn_impl, variant=variant)
    os.makedirs(out_dir, exist_ok=True)
    mesh_tag = "multi" if multi_pod else "single"
    suffix = f"_{variant}" if variant else ""
    path = os.path.join(out_dir, f"{arch}_{shape_name}_{mesh_tag}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        print(f"[ok] {arch:22s} {shape_name:12s} {mesh_tag:6s} "
              f"flops/dev={rec['hlo_flops_per_dev']:.3e} "
              f"bytes/dev={rec['hlo_bytes_per_dev']:.3e} "
              f"coll/dev={rec['collective_bytes_per_dev']:.3e} "
              f"dom={rec['dominant']} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--icq-grad", action="store_true",
                    help="compressed cross-pod grad combine (multi mesh)")
    ap.add_argument("--attn-impl", default="chunked")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        cells = ([args.shape] if args.shape
                 else list(shapes_for(cfg).keys()))
        for shape_name in cells:
            for mp in meshes:
                try:
                    run_cell(arch, shape_name, mp, icq_grad=args.icq_grad,
                             attn_impl=args.attn_impl, out_dir=args.out,
                             variant=args.variant)
                except Exception as e:
                    failures.append((arch, shape_name, mp, repr(e)))
                    print(f"[FAIL] {arch} {shape_name} "
                          f"{'multi' if mp else 'single'}: {e}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: "
                         + "; ".join(f"{a}/{s}/{m}" for a, s, m, _ in failures))
    print("all requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()
