"""device.idle_share.serve: 1 - (union of device operation intervals /
traced window), in percent, for the open-loop served cells."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    share = tr.idle_share()
    return None if share is None else 100.0 * share
