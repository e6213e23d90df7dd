"""A cell cut down to a size the CPU test run holds: the same harness,
traffic and comparison, over a few thousand rows with m=16 codebooks."""
import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(name: str, root: str = ROOT) -> dict:
    from bench import cells

    cell = cells.find_cell(name, root)
    cfg = copy.deepcopy(cell["config"])
    cfg["shapes"].update(n_learn=1024, n_base=8192, n_queries=256)
    cfg["assumed"]["generator"]["clusters"] = 32
    cfg["icq"]["train"].update(codebook_size=16, epochs=1, batch_size=256)
    cfg["icq"]["encode"]["chunk"] = 2048
    cfg["icq"]["index"].update(n_lists=16, n_probe=4)
    cell["config"] = cfg
    return cell


def run_tiny(name: str, *, seed: int = 7, seconds: float = 0.5,
             trace: bool = False, overrides=None, root: str = ROOT) -> dict:
    from bench import run

    out = run.run(name, seed, seconds, trace, require_tpu=False, root=root,
                  cell=tiny_cell(name, root), overrides=overrides)
    out.pop("_log", None)
    return out
