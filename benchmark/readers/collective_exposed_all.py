"""Share of the traced window in which a device ran a collective and no
compute, in %, averaged over chips, with XLA:TPU's asynchronous collectives
counted: ``collective_exposed`` plus the time of the fusions named
``async-collective-start.N`` / ``async-collective-done.N``, which open and
await a collective whose steps ride on compute fusions between them.  A
core runs its ``XLA Ops`` one after another (``collective_s`` equals
``collective_exposed_s`` in every trace of the dp4 cell), so while a start
or a done fusion runs nothing else computes.  Nothing to read where the
program holds no such fusion: ``collective_exposed`` is then the whole."""
import re

ASYNC_END = re.compile(r"^async-collective-(start|done)")


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    ends = sum(sec for name, sec in t["op_seconds"].items()
               if ASYNC_END.match(name))
    if not ends:
        return None
    return 100.0 * (t["collective_exposed_s"] + ends) / t["window_s"]
