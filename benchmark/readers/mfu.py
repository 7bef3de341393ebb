"""Model FLOP/s utilisation: the FLOPs per token the mathematics needs
(``ops_bytes``, from shapes) times tokens per second per chip, over the
chip's peak, in %.  An end-to-end utilisation, not a kernel's roofline
share."""


def read(ctx):
    v = ctx["values"]
    return 100.0 * v["flops_per_token"] * v["tokens_per_s_per_chip"] \
        / ctx["run"].peaks["flops_per_s"]
