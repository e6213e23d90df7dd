"""kernels.device_share: device time of the search kernels over the
device's busy time in the window, in percent (profiler trace)."""

# the fused Pallas kernels of kernels/batched_search.py, named in the
# trace by their jitted wrappers (crude_topk_pallas.1, ...)
KERNELS = (r"^(ivf_)?(crude|refine)_topk_pallas(\.\d+)?$",)


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    busy = tr.busy_s()
    t = tr.op_seconds(KERNELS)
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
