"""Mean duration, in ms, of the program's host spans of one name that
started inside the window."""


def read(ctx, span):
    ms = [(s.end - s.start) * 1e3 for s in ctx.get("spans", ())
          if s.name == span]
    return sum(ms) / len(ms) if ms else None
