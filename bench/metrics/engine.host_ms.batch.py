"""engine.host_ms.batch: host time of each ``Searcher.search`` call,
the ``repro.search`` span's duration minus the union of the
``repro.engine.wait`` spans inside it (``bench/program_spans.py``), mean
over the window's calls, in ms."""
import numpy as np

from bench import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    vals = spans.host_ns("repro.search") if spans else []
    return float(np.mean(vals)) / 1e6 if vals else None
