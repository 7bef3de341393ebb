"""``scale`` times the sum of one attribute over the sum of others, over
the program's host spans of one name that started inside the window and
carry them all: what a step did over what it ran (tokens committed over
slot-passes).  Nothing to read where no span has the attributes."""


def read(ctx, span, num, den, scale=1.0):
    rows = [s.attrs for s in ctx.get("spans", ())
            if s.name == span and num in s.attrs
            and all(d in s.attrs for d in den)]
    total = sum(a[d] for a in rows for d in den)
    if not total:
        return None
    return scale * sum(a[num] for a in rows) / total
