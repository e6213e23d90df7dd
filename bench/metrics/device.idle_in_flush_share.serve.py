"""device.idle_in_flush_share.serve: the share of the window's device
idle time during which the serving loop's worker (the host line that
holds the ``repro.serve.flush`` spans) is inside a flush, in percent
(``bench/program_spans.py``).  The rest of the idle time the worker
waits for requests or runs between flushes."""
from bench import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    worker = spans.line_of("repro.serve.flush")
    idle = spans.device_idle()
    if worker is None or not idle:
        return None
    flush = program_spans.union(
        [(s, e) for _, s, e in spans.named("repro.serve.flush", worker)])
    total = sum(e - s for s, e in idle)
    return 100.0 * program_spans.overlap(idle, flush) / total
