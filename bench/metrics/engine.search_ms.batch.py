"""engine.search_ms.batch: ``ResultMeta.wall_ms`` of each
``Searcher.search`` call (host clock around the engine call, ending in
``block_until_ready``), mean over the window's calls."""
import numpy as np


def read(ctx):
    vals = [c["meta"].wall_ms for c in ctx.get("calls") or []
            if c["meta"] is not None]
    return float(np.mean(vals)) if vals else None
