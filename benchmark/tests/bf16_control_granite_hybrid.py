"""The reference check's second reading for ``granite-4.0-h-micro``
(``bf16_control.py``'s method, ``bf16_control_olmo_hybrid.py``'s code by
import: both cells compare through ``serve_delta.check_request``): the
plain reference computed in bfloat16 throughout (weights, activations,
products, the convolution's taps and bias, the state-space state) stands
in for the program and goes through the cell's own comparison: for each
reference prompt it is handed, in the engine's place, a result whose
``logits`` are the stand-in's rows ``n - 1 .. n + 7`` of a teacher-forced
sequence.  bfloat16 is the nearest precision below the float32 the
configuration states, so the comparison must come out NOT fine on at
least one prompt.

    python3 benchmark/tests/bf16_control_granite_hybrid.py [--seed N] [--rehearse]

prints one line per reference prompt; without ``--rehearse`` it is the
published widths and needs the chip.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import bf16_control_olmo_hybrid as control  # noqa: E402 (sets the paths)

readings = control.readings


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--workload" not in argv:
        argv += ["--workload", "granite4h-micro-manychats"]
    if "--seed" not in argv:
        argv += ["--seed", "5900000003"]
    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())
