"""serve.queue_ms_mean: ``ResultMeta.queue_ms`` (submit to the dispatch
of the request's flush), mean over the window's completed requests."""
import numpy as np


def read(ctx):
    vals = [r["result"].meta.queue_ms for r in ctx.get("done") or []
            if r["result"].meta is not None
            and r["result"].meta.queue_ms is not None]
    return float(np.mean(vals)) if vals else None
