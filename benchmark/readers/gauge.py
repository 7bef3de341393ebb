"""Mean over the window of a program gauge sampled every 50 ms, times
``scale``; ``per`` divides by a number read from the engine (pages in the
pool, for a fill share)."""


def read(ctx, name, scale=1.0, per=None):
    xs = [g[name] for g in ctx.get("gauges", ()) if name in g]
    if not xs:
        return None
    v = scale * sum(xs) / len(xs)
    if per is not None:
        v /= float(getattr(ctx["engine"], per))
    return v
