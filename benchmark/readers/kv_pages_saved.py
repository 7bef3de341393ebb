"""Share of KV pages, in %, that the window layers' own pool saves: 1 -
(pages live in the full kind x full layers + pages live in the window
kind x window layers) over what whole contexts in every layer would hold
(the full kind's pages x all layers).  Means over the window's decode
steps, from the ``pages_live_full`` / ``pages_live_window`` attributes of
``generation/decode_step``; ``fn`` gives the number of window layers."""
from harness import resolve


def read(ctx, fn, span="generation/decode_step"):
    steps = [s.attrs for s in ctx.get("spans", ())
             if s.name == span and "pages_live_window" in s.attrs]
    if not steps:
        return None
    cfg = ctx["cfg"]
    layers, n_window = cfg["num_hidden_layers"], resolve(fn)(cfg)
    full = sum(a["pages_live_full"] for a in steps)
    window = sum(a["pages_live_window"] for a in steps)
    if full <= 0:
        return None
    held = (layers - n_window) * full + n_window * window
    return 100.0 * (1.0 - held / (layers * full))
