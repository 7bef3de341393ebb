"""The ``p``-th percentile of a numeric attribute of the program's host
spans of one name that started inside the window (``queue_wait_ms`` on
``generation/sequence``)."""
from harness import quantile


def read(ctx, span, attr, p):
    xs = [s.attrs[attr] for s in ctx.get("spans", ())
          if s.name == span and attr in s.attrs]
    return quantile(xs, p / 100.0) if xs else None
