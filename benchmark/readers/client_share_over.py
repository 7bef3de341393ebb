"""The share, in percent, of one of the load generator's records (see
``client_percentile.py`` for the fields) that lies above ``times`` the
median of that record: ``itl`` at 2 is the share of token gaps that
carried a stall."""
from harness import share_over


def read(ctx, field, times):
    xs = ctx.get("clients", {}).get(field)
    return share_over(xs, times) if xs else None
