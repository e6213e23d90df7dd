"""Find a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic file, the driver of the traffic's kind
and its per-layer metric readers.  A later cell, mix, kind of traffic or
metric is a new file and a new entry; nothing here changes for it."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found
    or does not hold together."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(name: str, root: str = ROOT) -> dict:
    """The resolved cell: its workload entry, configuration, traffic mix
    and the metric entries it reports with and without a trace."""
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise CellError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(work)}")
    w = work[name]
    confs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in confs:
        raise CellError(f"workload {name!r} names config {w['config']!r}, "
                        "which BENCHMARK.json does not list")
    conf = confs[w["config"]]
    return {
        "workload": w,
        "config": load_config_file(os.path.join(root, conf["file"])),
        "traffic": load_traffic(w["traffic"], root),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "run_seconds": bench["run_seconds"],
        "root": root,
    }


def load_config_file(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str, root: str = ROOT) -> dict:
    """A configuration by name, from ``bench/configs/<name>.json``."""
    return load_config_file(os.path.join(root, "bench", "configs",
                                         f"{name}.json"))


def load_traffic(name: str, root: str = ROOT) -> dict:
    """A traffic mix by name, from ``bench/traffic/<name>.json``; its
    ``kind`` names its driver, ``bench/traffic_kinds/<kind>.py``."""
    path = os.path.join(root, "bench", "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise CellError(f"no traffic file {os.path.relpath(path, root)}")
    with open(path) as f:
        mix = json.load(f)
    traffic_kind(mix.get("kind"), root)
    return mix


def _load(kind: str, name: str, root: str, what: str):
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not isinstance(name, str) or not os.path.exists(path):
        raise CellError(f"no {what} {os.path.relpath(path, root)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_kind(kind: str, root: str = ROOT):
    """The driver module of a traffic kind: ``warm(searcher, pool, mix)``
    returns its state (with the ``backend`` that served the warm-up),
    ``window(state, pool, mix, seconds, seed, span)`` runs the measured
    window and returns its answers, counts, end-to-end metrics and the
    records the per-layer readers take, ``close(state)`` stops it."""
    mod = _load("traffic_kinds", kind, root, f"driver for traffic kind "
                f"{kind!r}")
    for fn in ("warm", "window", "close"):
        if not callable(getattr(mod, fn, None)):
            raise CellError(f"traffic kind {kind!r} has no {fn}()")
    return mod


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _load("metrics", name, root,
                 f"reader for per-layer metric {name!r}").read


def reference_model(searcher, cfg: dict) -> dict:
    """What the reference takes from a fitted index, copied to the host
    so that the program's state can be freed first: the codebooks and
    the fast-codebook set, and for IVF the coarse centroids and the
    program's inverted lists (held to the centroids, not searched)."""
    import numpy as np

    idx = searcher.index
    model = {"C": np.asarray(idx.C, np.float32),
             "fast": np.asarray(idx.structure.fast_mask, bool)}
    ivf = getattr(idx, "ivf", None)
    if ivf is not None:
        model.update(centroids=np.asarray(ivf.centroids, np.float32),
                     program_lists=np.asarray(ivf.lists, np.int32),
                     n_probe=int(cfg["icq"]["index"]["n_probe"]))
    return model
