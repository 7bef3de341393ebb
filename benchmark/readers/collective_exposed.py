"""Share of the traced window in which a device ran a collective and no
compute, in %, averaged over chips."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0 or t["collective_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
