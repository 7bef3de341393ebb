"""Peak device memory on the fullest chip, in GB (1e9 bytes): the result
line's ``memory_peak_bytes`` (``harness.Run.device_block``)."""


def read(ctx):
    peak = ctx["run"].device["memory_peak_bytes"]
    return peak / 1e9 if peak else None
