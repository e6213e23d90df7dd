"""search_kernels_roofline: the least time the search algorithm's own
work allows on this chip (``bench/workcount.py``: bytes over HBM
bandwidth or operations over the peak rate, whichever is larger), over
the device time of the search kernels, in percent.

The work per batch is counted from the reference's answers to the
window's sampled batches (rows scanned and rows passing eq. 2, per
query and distinct per batch) and scaled to the traced batches.  Where
the search kernels run on several chips, each does a share of that work
in the kernel time averaged over the chips: the least time is divided
by the number of chips whose trace holds a search kernel."""
import numpy as np

from bench import peaks, workcount

KERNELS = (r"^(ivf_)?(crude|refine)_topk_pallas(\.\d+)?$",)


def read(ctx):
    tr, ref = ctx.get("trace"), ctx.get("reference")
    calls = ctx.get("calls")
    if tr is None or ref is None or not calls:
        return None
    t_kernels = tr.op_seconds(KERNELS)
    if t_kernels <= 0:
        return None
    b = ctx["batch"]
    blocks = len(ref["rows_scanned"])
    if len(ref["passed"]) != blocks * b:
        return None
    tr_cfg = ctx["config"]["icq"]["train"]
    k, m = tr_cfg["num_codebooks"], tr_cfg["codebook_size"]
    k_fast = int(np.sum(ctx["model"]["fast"]))
    per_batch = (
        workcount.crude(scanned=ref["scanned"].sum() / blocks,
                        rows_read=ref["rows_scanned"].mean(), k_fast=k_fast)
        + workcount.refine(survivors=ref["passed"].sum() / blocks,
                           rows_read=ref["rows_passed"].mean(), k=k,
                           k_fast=k_fast)
        + workcount.tables(nq=b, k=k, m=m))
    if "centroids" in ctx["model"]:
        n_lists, d = ctx["model"]["centroids"].shape
        per_batch = per_batch + workcount.probe(nq=b, n_lists=n_lists, d=d)
    least, _ = workcount.least_seconds(per_batch,
                                       peaks.peaks_for(ctx["device_kind"]))
    return 100.0 * least * len(calls) / tr.op_chips(KERNELS) / t_kernels
