"""device.peak_hbm_gb: ``memory_stats()["peak_bytes_in_use"]`` of the
fullest chip, read right after the window, in GB (1e9 bytes)."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
