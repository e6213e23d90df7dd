"""engine.search_ms.serve: ``ResultMeta.wall_ms`` of the flush that
served each completed request, mean over requests (a flush counts once
per request it carried)."""
import numpy as np


def read(ctx):
    vals = [r["result"].meta.wall_ms for r in ctx.get("done") or []
            if r["result"].meta is not None]
    return float(np.mean(vals)) if vals else None
