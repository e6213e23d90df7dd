"""serve.batch_fill_mean: ``ResultMeta.batch_fill`` (real rows over the
tile of the flush that served a request), mean over completed requests,
in percent."""
import numpy as np


def read(ctx):
    vals = [r["result"].meta.batch_fill for r in ctx.get("done") or []
            if r["result"].meta is not None
            and r["result"].meta.batch_fill is not None]
    return 100.0 * float(np.mean(vals)) if vals else None
