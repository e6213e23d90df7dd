#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``), whose ``kind`` names its driver
(``bench/traffic_kinds/<kind>.py``).  One run:

  set-up    the configuration's data set made on the device, exact
            neighbours of the query pool, ``icq_session(cfg).fit`` on
            the learn rows, ``session.index`` over the base rows
            (row-sharded over the cell's chips where it asks for more
            than one), and the traffic's shapes warmed by its driver
            (``setup_s``: process start to here);
  window    ``--seconds`` of the traffic, in an order drawn from
            ``--seed``, through the program's public entries
            (``Searcher.search`` or ``ServingLoop.submit``), under the
            profiler with ``--trace 1``;
  check     the program's state freed, then the reference
            (``reference.py``) on the stored codes and on a sample of
            the window's answers drawn from the seed (``verdict.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace
1`` ``breakdown``, and last ``checks`` (each compared number with its
limit, also the last lines of standard error).  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                        # noqa: E402
import gc                                              # noqa: E402
import json                                            # noqa: E402
import os                                              # noqa: E402
import shutil                                          # noqa: E402
import sys                                             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")
SAMPLE_ANSWERS = 1024          # answers of the window held to the reference
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def require_chip(chips: int):
    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is {platform!r}")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def enable_compile_cache(path: str = CACHE_DIR) -> str:
    """JAX's persistent cache at a fixed directory inside the checkout."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the search programs carry the stored codes as constants (8 MB
    # each at 1M rows): a size cap from the environment would evict
    # them between runs
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileClock:
    """Backend compiles and their seconds, from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------- set-up --

def make_data(cfg: dict):
    """The configuration's data set: learn and base rows on the device,
    the query pool on the host, and the pool's exact neighbours (host).
    One fixed draw from the configuration's ``data_seed``, as SIFT1M is
    one fixed set."""
    import numpy as np

    from bench import gen, truth

    sh = cfg["shapes"]
    learn, base, pool = gen.make_parts(
        cfg["assumed"]["data_seed"],
        (sh["n_learn"], sh["n_base"], sh["n_queries"]), sh["d"],
        cfg["assumed"]["generator"])
    gt, _ = truth.exact_neighbours(pool, base, sh["k"])
    return learn, base, np.asarray(pool), np.asarray(gt)


def build(cfg: dict, learn, base, overrides=None, devices=None):
    """Fit and index through the program's front door, keyed by the
    configuration's ``data_seed``: one deployment, one index.  Over more
    than one device the index is row-sharded on a mesh with one "data"
    axis over them (``session.index(mesh=)``); over one, no mesh."""
    import jax

    from bench import gen
    from repro.api import ICQConfig, icq_session

    icq = ICQConfig.from_dict(cfg["icq"])
    if overrides:
        icq = icq.with_overrides(overrides)
    key = gen.seed_key(cfg["assumed"]["data_seed"])
    session = icq_session(icq)
    session.fit(learn, key=jax.random.fold_in(key, 1))
    mesh = None
    if devices is not None and len(devices) > 1:
        mesh = jax.make_mesh((len(devices),), ("data",), devices=devices,
                             axis_types=(jax.sharding.AxisType.Auto,))
    return session.index(base, mesh=mesh, key=jax.random.fold_in(key, 2))


# --------------------------------------------------------------- run ---

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True, cell=None,
        overrides=None) -> dict:
    """One run of one cell; returns the result object.  ``cell`` and
    ``require_tpu`` let a test drive a reduced cell on the CPU."""
    from bench import cells

    cell = cell or cells.find_cell(workload, root)
    st = prepare(cell, require_tpu=require_tpu, overrides=overrides)
    return measure(st, seed, seconds, trace, root=root)


def prepare(cell: dict, *, require_tpu: bool = True, overrides=None,
            backend: str = "pallas") -> dict:
    """Set-up: the data set, its exact neighbours, the fitted index and
    the traffic's shapes warmed.  Returns the state ``measure`` runs.
    On the chip the warm-up must be served by ``backend`` (None: any;
    a control whose path fails over to jnp is still a control)."""
    import numpy as np

    chips = int(cell["workload"]["chips"])
    if require_tpu:
        devices = require_chip(chips)
        enable_compile_cache()
    else:
        import jax
        devices = jax.devices()
    clock = CompileClock()
    phases = {"start": time.perf_counter() - T_START}

    def phase(name):
        phases[name] = time.perf_counter() - T_START
        phases[name + "_compile_s"] = clock.seconds

    from bench import cells, reference

    cfg, mix = cell["config"], cell["traffic"]
    learn, base, pool, gt = make_data(cfg)
    learn_var = np.asarray(reference.learn_variance(learn))
    phase("data")
    devices = devices[:chips]
    searcher = build(cfg, learn, base, overrides, devices)
    phase("index")
    del learn

    kind = cells.traffic_kind(mix["kind"], cell.get("root", cells.ROOT))
    traffic = kind.warm(searcher, pool, mix)
    served = traffic["backend"]
    if require_tpu and backend is not None and served != backend:
        raise RuntimeError(f"the warm-up was served by {served!r}, not the "
                           f"configuration's {backend} kernels")
    phase("warm")
    return {"cell": cell, "devices": devices, "clock": clock,
            "base": base, "learn_var": learn_var, "pool": pool, "gt": gt,
            "searcher": searcher, "kind": kind, "traffic": traffic,
            "backend": served, "setup_s": time.perf_counter() - T_START,
            "compiles_setup": clock.count, "phases": phases}


def measure(st: dict, seed: int, seconds: float, trace: bool, *,
            root: str = ROOT, keep: bool = False) -> dict:
    """The window and the check.  Unless ``keep``, the program's state is
    freed before the reference runs (the reference then cannot set the
    memory peak); ``keep`` lets one set-up serve several windows."""
    import jax
    import numpy as np

    from bench import cells, truth, verdict

    cell, devices, clock = st["cell"], st["devices"], st["clock"]
    cfg, mix = cell["config"], cell["traffic"]
    k = int(cfg["shapes"]["k"])
    pool, gt, backend = st["pool"], st["gt"], st["backend"]
    searcher, kind = st["searcher"], st["kind"]
    compiles_before = clock.count

    trace_dir = os.path.join(OUT_DIR, "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win = kind.window(st["traffic"], pool, mix, seconds, seed, _span)
    if trace:
        jax.profiler.stop_trace()
    compiles_window = clock.count - compiles_before
    stats = devices[0].memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # ----- what the window produced, on the host
    answers = win["answers"]
    if not answers:
        raise RuntimeError("the window answered no request")
    attempted = int(win["attempted"])
    failed = int(win["lost"]) + sum(len(a["rows"]) for a in answers
                                    if a["backend"] != backend)
    recall = float(np.mean(np.concatenate([
        truth.recall_per_query(a["ids"], gt[a["rows"]], k)
        for a in answers])))
    per = len(answers[0]["rows"])
    rng = np.random.default_rng([seed % (1 << 63), 2])
    pick = rng.choice(len(answers), size=min(len(answers),
                                             -(-SAMPLE_ANSWERS // per)),
                      replace=False)
    s_rows = np.concatenate([answers[j]["rows"] for j in pick])
    s_ids = np.concatenate([answers[j]["ids"] for j in pick])
    s_dists = np.concatenate([answers[j]["dists"] for j in pick])
    e2e = dict(win["metrics"], recall_at_10=recall)

    # ----- free the program, then the reference
    model = cells.reference_model(searcher, cfg)
    program_codes = np.asarray(searcher.index.codes)
    program_sigma = float(np.asarray(searcher.index.structure.sigma))
    if not keep:
        kind.close(st["traffic"])
        st["searcher"] = st["traffic"] = searcher = None
        gc.collect()
    t_check = time.perf_counter()
    numbers, ref = reference_check(cfg, st["base"], st["learn_var"], pool,
                                   model, program_codes, s_rows, s_ids,
                                   s_dists,
                                   rounded=devices[0].platform == "tpu")
    check_s = time.perf_counter() - t_check
    correct, checks = verdict.decide(numbers, verdict.load_limits())
    correct = correct and failed == 0

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    if trace:
        from bench import tracing
        red = tracing.Reduced(tracing.from_xplane(
            tracing.find_xplane(trace_dir)))
        device.update(busy_s=red.busy_s(), window_s=red.window_s)
        layer_ctx = dict(win["layer"], trace=red, reference=ref, config=cfg,
                         model=model, device_kind=devices[0].device_kind,
                         memory_peak_bytes=peak, mix=mix)
        metrics = {}
        for m in cell["per_layer"]:
            v = cells.metric_reader(m["name"], root)(layer_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out.update(metrics=metrics, device=device,
                   breakdown=red.breakdown(10))
    else:
        e2e["setup_s"] = st["setup_s"]
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        out.update(metrics={n: {"value": float(v), "unit": units[n]}
                            for n, v in e2e.items() if n in units},
                   device=device)
    out["checks"] = checks
    out["_log"] = {"setup_s": st["setup_s"],
                   "compiles_setup": st["compiles_setup"],
                   "compiles_window": compiles_window,
                   "compile_s": clock.seconds, "check_s": check_s,
                   "phases": st["phases"],
                   "sigma_program": program_sigma,
                   "sigma_reference": float(model["sigma"]),
                   "model_sha": _digest(model["C"], model["fast"]),
                   "codes_sha": _digest(program_codes),
                   "data_sha": _digest(pool, gt),
                   "bytes_limit": stats.get("bytes_limit"), **e2e}
    return out


def _digest(*arrays) -> str:
    """A short fingerprint of host arrays: two runs that built the same
    index print the same one."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def reference_check(cfg, base, learn_var, pool, model, program_codes,
                    rows, ids, dists, *, rounded: bool):
    """The compared numbers, and the reference's answers with their
    per-query counts (the work behind the roofline metric).  Completes
    ``model`` with what the reference builds itself: the eq. 11 margin
    and, for IVF, its own inverted lists.  ``rounded``: the platform's
    default f32 product, which the program's coarse assignment and probe
    run at, is one bfloat16 pass (the TPU's)."""
    import numpy as np

    from bench import reference, verdict

    icm = int(cfg["icq"]["encode"]["icm_iters"])
    ref_codes = reference.icm_codes(base, model["C"], iters=icm)
    numbers = {"codes_mismatch": verdict.codes_mismatch(
        program_codes, np.asarray(ref_codes))}
    del ref_codes
    model["sigma"] = reference.margin_sigma(
        learn_var, model["C"], model["fast"],
        float(cfg["icq"]["train"].get("margin_scale", 1.0)))
    if "centroids" in model:
        (model["lists"], model["ambiguous"],
         numbers["lists_misfiled"]) = reference.ivf_lists(
            base, model["centroids"], model["program_lists"],
            rounded=rounded)
        model.update(base=base, rounded=rounded)
    k = int(cfg["shapes"]["k"])
    ref = reference.search(pool[rows], program_codes, model, topk=k,
                           answers=ids)
    numbers.update(
        ids_missed=verdict.ids_missed(ids, ref["answer_dists"], ref["dists"],
                                      ref["lut_range"], ref["reachable"]),
        dist_gap=verdict.dist_gap(dists, ref["answer_dists"],
                                  ref["lut_range"]))
    return numbers, ref


def emit(out: dict) -> None:
    log = out.pop("_log", {})
    print(json.dumps({"bench_log": log}), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro.api  # noqa: F401
    except ImportError as e:
        print(f"bench: the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
