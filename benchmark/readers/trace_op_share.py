"""Share of the device's busy time spent in operations whose HLO text
matches ``pattern`` (a regular expression), in %."""
import re


def read(ctx, pattern):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    rx = re.compile(pattern)
    hit = sum(sec for name, sec in t["op_seconds"].items()
              if rx.search(t["op_text"][name]))
    return 100.0 * hit / t["busy_s"] if hit else None
