"""The reference check's second reading for ``deepseek-v2``:
``bf16_control_command_a_plus.py``'s method and code, by import (the plain
reference computed in bfloat16 throughout stands in for the program and
goes through the cell's own comparison, ``serve_state.check_request``,
teacher-forced from the seed, on weights drawn from the seed alone: this
router has no selection bias).  bfloat16 is the nearest precision below
the float32 the configuration states, so the comparison must come out NOT
fine, and does on every prompt.

    python3 benchmark/tests/bf16_control_deepseek_v2.py [--seed N] [--rehearse]

prints one line per reference prompt; without ``--rehearse`` it is the
published widths and needs the chip.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bf16_control_command_a_plus as sibling  # noqa: E402

readings = sibling.readings


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if "--workload" not in argv:
        argv += ["--workload", "deepseek-v2-docqa"]
    if "--seed" not in argv:
        argv += ["--seed", "5600000003"]
    return sibling.main(argv)


if __name__ == "__main__":
    sys.exit(main())
