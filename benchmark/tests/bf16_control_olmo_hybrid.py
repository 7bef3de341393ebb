"""The reference check's second reading for ``olmo-hybrid-7b``
(``bf16_control.py``'s method): the plain reference computed in bfloat16
throughout (weights, activations, products, the convolution's taps, the
recurrent state) stands in for the program and goes through the cell's
own comparison, ``serve_delta.check_request``, the function that decides
``correct`` for a compared request: for each reference prompt it is
handed, in the engine's place, a result whose ``logits`` are the
stand-in's rows ``n - 1 .. n + 7`` of a teacher-forced sequence.  bfloat16 is the
nearest precision below the float32 the configuration states, so the
comparison must come out NOT fine on at least one prompt.

    python3 benchmark/tests/bf16_control_olmo_hybrid.py [--seed N] [--rehearse]
        [--standin stated|throughout|fp8 [--entry float32|bfloat16]]

prints one line per reference prompt; without ``--rehearse`` it is the
published widths and needs the chip.
"""
import argparse
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (BENCH, os.path.dirname(BENCH))
                if p not in sys.path]


def readings(cell, seed: int) -> list:
    """``[(prompt_len, fine, share_of_range), ...]`` over the mix's
    ``reference_prompts``, sequences teacher-forced from the seed."""
    import jax
    import jax.numpy as jnp

    import serve_delta
    import serve_state
    import traffic

    cfg, mix = cell.cfg, cell.mix
    new = serve_state.CHECK_NEW_TOKENS
    ref = cell.reference()
    params = ref.params_from_scope(
        serve_delta.seeded_scope(cell.builder(), cfg, mix, seed), cfg)
    low = jax.jit(lambda p, ids, rows: ref.forward(
        p, ids, cfg, rows, dtype=jnp.bfloat16))
    full = serve_delta.jitted_forward(ref, cfg)
    pad = serve_state.check_pad(mix)
    out = []
    for j, n in enumerate(mix["reference_prompts"]):
        seq = traffic.token_ids(seed, 900000 + j, n + new - 1,
                                cfg["vocab_size"])
        ids = np.zeros((pad,), "int32")
        ids[:len(seq)] = seq
        logits = low(params, ids, np.arange(n - 1, n - 1 + new))
        # row n - 1 + k yields token n + k: the tokens a program would
        # have returned are the teacher's, with one more at the end
        res = {"tokens": seq[n:] + [1], "finish": "length",
               "logits": list(np.asarray(logits, np.float32))}
        # (the last token is never fed back: rows stop at n + 7)
        fine, got = serve_delta.check_request(
            full, params, cell.tolerance, pad, seq[:n], res)
        print(f"[bf16 control] seed {seed} prompt {n}: the reference in "
              f"bfloat16 throughout is off the float32 reference "
              f"by {got['rel']:.4g} of its range (tolerance "
              f"{cell.tolerance:.4g}): the cell's comparison says "
              f"{'fine' if fine else 'NOT correct'}", flush=True)
        out.append((n, fine, got["rel"]))
    return out


def main(argv=None) -> int:
    import harness

    argv = sys.argv[1:] if argv is None else list(argv)
    if "--standin" in argv:
        # 'stated', 'fp8' or this file's own reading through the shared
        # stand-ins, judged by the bfloat16 entry (standins.py)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import standins

        return standins.control(argv, "olmo-hybrid7b-longdoc")
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="olmo-hybrid7b-longdoc")
    ap.add_argument("--seed", type=int, default=4100000003)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    got = readings(cell, args.seed)
    failed = [n for n, fine, _ in got if not fine]
    print(f"[bf16 control] not correct on prompts {failed} of "
          f"{[n for n, _, _ in got]}: the check "
          f"{'fails' if failed else 'PASSES'} bfloat16", flush=True)
    return 0 if failed else 1


if __name__ == "__main__":
    sys.exit(main())
