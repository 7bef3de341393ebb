"""Mean, times ``scale``, of a numeric attribute of the program's host
spans of one name that started inside the window; ``per`` divides by a
number of the configuration (a key of its file).  The generation engine
writes what its gauges read at a decode step onto the step's span
(``experts_touched``, ``pages_live_window``), so a mean over the window
needs no sampler."""


def read(ctx, span, attr, scale=1.0, per=None):
    xs = [s.attrs[attr] for s in ctx.get("spans", ())
          if s.name == span and attr in s.attrs]
    if not xs:
        return None
    v = scale * sum(xs) / len(xs)
    if per is not None:
        v /= float(ctx["cfg"][per])
    return v
