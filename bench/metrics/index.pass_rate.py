"""index.pass_rate: ``SearchResult.pass_rate`` (share of scanned rows
that pass the eq. 2 margin test), mean over the window's calls, in
percent.  Read only where every call is one full tile, since the engine
averages it over padded rows otherwise."""
import numpy as np


def read(ctx):
    calls = ctx.get("calls") or []
    if not calls or any(len(c["rows"]) != ctx["batch"] for c in calls):
        return None
    return 100.0 * float(np.mean([float(c["pass_rate"]) for c in calls]))
