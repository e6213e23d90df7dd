"""shard.collective_share.batch: device time of the cross-chip merge's
collectives over the device's busy time in the window, both averaged
over the chips, in percent (profiler trace).  The collectives are the
``all-gather`` and ``all-reduce`` ops, their ``-start`` and ``-done``
halves included; XLA names the all-reduce of ``jax.lax.psum`` after it
(``psum.7``).  None where no collective ran, as on one chip."""

COLLECTIVES = (r"^(all-gather|all-reduce|psum)(-start|-done)?(\.\d+)?$",)


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    busy = tr.busy_s()
    t = tr.op_seconds(COLLECTIVES)
    if busy <= 0 or t <= 0:
        return None
    return 100.0 * t / busy
