"""Device idle share of the traced window: 100 x (1 - busy / window),
busy being the union of the device-op intervals, averaged over chips."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
