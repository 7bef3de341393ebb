"""The reference check's second reading for ``mistral-7b-v0.1``, which had
none until PR 67: the stand-ins of ``standins.py`` through
``serve.reference_check``'s comparison (a dense decoder: no router, no
near-tie rule), judged by the ``bfloat16`` entry of the configuration's
``check_tolerance``.  ``throughout`` (the default) is the reference in
bfloat16 throughout, ``stated`` what ``as_run.bfloat16`` states, ``fp8``
the control that entry has to fail.

    python3 benchmark/tests/bf16_control_mistral.py [--seed N] [--rehearse]
        [--standin stated|throughout|fp8] [--entry float32|bfloat16]

prints one line per reference prompt; without ``--rehearse`` it is the
published widths and needs the chip.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import standins  # noqa: E402  (numpy only until it runs)


def main(argv=None) -> int:
    return standins.control(sys.argv[1:] if argv is None else list(argv),
                            "mistral7b-longprompt")


if __name__ == "__main__":
    sys.exit(main())
