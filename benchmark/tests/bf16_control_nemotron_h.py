"""The reference check's second reading for ``nemotron3-super-120b-a12b``:
``bf16_control_gigachat35.py``'s method and code, by import (the plain
reference computed in bfloat16 throughout, here the state-space state and
its decay too, stands in for the program and goes through the cell's own
comparison, ``serve_state.check_request``, teacher-forced from the seed, on
weights whose selection bias AND decay constants are drawn from the seed:
``serve_share.seeded_scope``).  bfloat16 is the nearest precision below the
float32 the configuration states, so the comparison must come out NOT fine,
and does on every prompt.

    python3 benchmark/tests/bf16_control_nemotron_h.py [--seed N] [--rehearse]

prints one line per reference prompt; without ``--rehearse`` it is the
published widths and needs the chip.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import bf16_control_gigachat35 as control  # noqa: E402 (sets the paths)

readings = control.readings


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--workload" not in argv:
        argv += ["--workload", "nemotron3-super-agentfleet"]
    if "--seed" not in argv:
        argv += ["--seed", "6300000090"]
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
