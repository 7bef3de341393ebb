"""The reference check's second reading for ``smallthinker-21b-a3b``: the
plain reference computed in bfloat16 throughout (weights, activations,
products) stands in for the program and goes through the check as
``serve.reference_check`` makes it: its logits of the compared rows
against the float32 reference's, which is handed the stand-in's router
logits (``cfg["_program_router"]``) and takes its choice at a near tie.
bfloat16 is the nearest precision below the float32 the configuration
states, so at least one prompt must come out over ``share_of_range``.

    python3 benchmark/tests/bf16_control.py [--seed N] [--rehearse]
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

CHECK_NEW_TOKENS = 9                 # serve.CHECK_NEW_TOKENS


def readings(cell, seed: int) -> list:
    """``[(prompt_len, share_of_range), ...]`` over the mix's
    ``reference_prompts``, sequences teacher-forced from the seed."""
    import jax
    import jax.numpy as jnp

    import harness
    import traffic

    cfg, mix = cell.cfg, cell.mix
    lens = list(mix["reference_prompts"])
    gen = cell.builder().engine(
        cfg, mix, num_slots=2,
        buckets=[min(mix["engine"]["prefill_buckets"])])
    gen.close()
    harness.seeded_weights(
        gen.scope, [n for n in gen.scope.local_var_names()
                    if n.startswith(gen.name + ".")
                    and n not in gen.cache_names], seed)
    ref = cell.reference()
    params = ref.params_from_scope(gen.scope, cfg, gen.name)

    def low(p, ids, rows):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
        got, router = ref.forward(p, ids, cfg, rows, keep_router=True)
        return got.astype(jnp.float32), router.astype(jnp.float32)

    low = jax.jit(low)
    pad = -(-(max(lens) + CHECK_NEW_TOKENS) // 128) * 128
    out = []
    for j, n in enumerate(lens):
        seq = traffic.token_ids(seed, 900000 + j, n + CHECK_NEW_TOKENS - 1,
                                cfg["vocab_size"])
        ids = np.zeros((pad,), "int32")
        ids[:len(seq)] = seq
        rows = np.arange(n - 1, n - 1 + CHECK_NEW_TOKENS)
        got, router = (np.asarray(a) for a in low(params, ids, rows))
        cfg["_program_router"] = {"ids": list(seq), "first_row": n - 1,
                                  "logits": router}
        want = np.asarray(jax.jit(
            lambda p, i, r: ref.forward(p, i, cfg, r))(params, ids, rows))
        del cfg["_program_router"]
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        print(f"[bf16 control] seed {seed} prompt {n}: the reference in "
              f"bfloat16 throughout, its router choices offered at near "
              f"ties, is off the float32 reference by {rel:.4g} of its "
              f"range (tolerance {cell.tolerance:.4g})", flush=True)
        out.append((n, rel))
    return out


def main(argv=None) -> int:
    import harness

    argv = sys.argv[1:] if argv is None else list(argv)
    if "--standin" in argv:
        # 'stated', 'fp8' or this file's own reading through the shared
        # stand-ins, judged by the bfloat16 entry (standins.py)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import standins

        return standins.control(argv, "smallthinker21b-mixedlen")
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="smallthinker21b-mixedlen")
    ap.add_argument("--seed", type=int, default=2800000003)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    got = readings(cell, args.seed)
    failed = [n for n, rel in got if rel > cell.tolerance]
    print(f"[bf16 control] over the tolerance on prompts {failed} of "
          f"{[n for n, _ in got]}: the check "
          f"{'fails' if failed else 'PASSES'} bfloat16", flush=True)
    return 0 if failed else 1


if __name__ == "__main__":
    sys.exit(main())
