"""serve.submit_ms_p95: duration of ``ServingLoop.submit`` (the
``repro.serve.submit`` span: embed, checks and the locked enqueue), 95th
percentile over the window's requests, in ms."""
import numpy as np

from bench import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    vals = spans.durations_ns("repro.serve.submit") if spans else []
    return float(np.percentile(vals, 95)) / 1e6 if vals else None
