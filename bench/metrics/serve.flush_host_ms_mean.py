"""serve.flush_host_ms_mean: host time of each flush of the serving
loop's worker, the ``repro.serve.flush`` span's duration minus the union
of the ``repro.engine.wait`` spans inside it
(``bench/program_spans.py``), mean over the window's flushes, in ms."""
import numpy as np

from bench import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    vals = spans.host_ns("repro.serve.flush") if spans else []
    return float(np.mean(vals)) / 1e6 if vals else None
