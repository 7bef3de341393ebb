"""A number the driver measured itself, by key."""


def read(ctx, key, scale=1.0):
    v = ctx["values"].get(key)
    return None if v is None else v * scale
