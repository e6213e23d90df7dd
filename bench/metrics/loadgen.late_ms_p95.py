"""loadgen.late_ms_p95: how late the open-loop generator sent its
requests (sent minus due, ms), 95th percentile over the window."""
import numpy as np

from bench import loadgen


def read(ctx):
    recs = ctx.get("records")
    if not recs:
        return None
    return float(np.percentile(loadgen.late_ms(recs), 95))
