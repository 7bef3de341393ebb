"""The reference check's second reading for ``longcat-flash-chat``:
``bf16_control_lfm2.py``'s method and code, by import (the plain reference
computed in bfloat16 throughout stands in for the program and goes through
the cell's own comparison, ``serve_state.check_request``, teacher-forced
from the seed), on weights drawn from the seed and the selection bias
drawn as the builder draws it (``serve_blocks.seeded_scope`` then
``longcat_flash_engine.seed_expert_bias``: the chunk driver draws none).
bfloat16 is the nearest precision below the float32 the configuration
states, so the comparison must come out NOT fine, and does on every
prompt.

    python3 benchmark/tests/bf16_control_longcat_flash.py [--seed N] [--rehearse]

prints one line per reference prompt; without ``--rehearse`` it is the
published widths and needs the chip.
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (BENCH, os.path.dirname(BENCH),
                            os.path.dirname(os.path.abspath(__file__)))
                if p not in sys.path]

import bf16_control_lfm2  # noqa: E402  (numpy only until it runs)

_lfm2_readings = bf16_control_lfm2.readings


def seeded_scope(builder, cfg, mix, seed):
    import serve_blocks

    scope = serve_blocks.seeded_scope(builder, cfg, mix, seed)
    builder.seed_expert_bias(scope, cfg)
    return scope


def readings(cell, seed: int) -> list:
    """``[(prompt_len, fine, share_of_range), ...]`` over the mix's
    ``reference_prompts``."""
    import serve_state

    theirs = serve_state.seeded_scope
    serve_state.seeded_scope = seeded_scope
    try:
        return _lfm2_readings(cell, seed)
    finally:
        serve_state.seeded_scope = theirs


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if "--workload" not in argv:
        argv += ["--workload", "longcat-flash-agentchat"]
    if "--seed" not in argv:
        argv += ["--seed", "6600000003"]
    bf16_control_lfm2.readings = readings   # what its ``main`` calls
    try:
        return bf16_control_lfm2.main(argv)
    finally:
        bf16_control_lfm2.readings = _lfm2_readings


if __name__ == "__main__":
    sys.exit(main())
