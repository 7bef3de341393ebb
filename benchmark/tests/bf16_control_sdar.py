"""The reference check's second reading for ``sdar-30b-a3b-chat``
(``bf16_control.py``'s method; that file's forward takes no mask): the
plain reference computed in bfloat16 throughout (weights, activations,
products) stands in for the program and goes through the cell's own
comparison, ``serve_blocks.check_request``, the function that decides
``correct`` for a request: for each reference prompt it is handed, in
the engine's place, a result of ``check_blocks`` blocks of two denoising
passes and a commit pass (the block all undecided behind the prompt's
tail, half decided, clean) whose ``logits`` and ``router_logits`` are the
stand-in's.  bfloat16 is the nearest precision below the float32 the
configuration states, so the comparison must come out NOT fine on at
least one prompt.

    python3 benchmark/tests/bf16_control_sdar.py [--seed N] [--rehearse]
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
    """``[(prompt_len, fine, largest share_of_range over its passes),
    ...]`` over the mix's ``reference_prompts``, sequences teacher-forced
    from the seed."""
    import jax
    import jax.numpy as jnp

    import serve_blocks
    import traffic

    cfg, mix = cell.cfg, cell.mix
    B = int(cfg["assumed"]["generation"]["block_length"])
    lens = list(mix["reference_prompts"])
    n_blocks = int(mix["check_blocks"])
    ref = cell.reference()
    params = ref.params_from_scope(
        serve_blocks.seeded_scope(cell.builder(), cfg, mix, seed), cfg)

    def low(p, ids, masked, rows):
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
        got, router = ref.forward(p, ids, masked, cfg, rows,
                                  keep_router=True)
        return got.astype(jnp.float32), router.astype(jnp.float32)

    low = jax.jit(low)
    full = serve_blocks.jitted_forward(ref, cfg)
    pad = serve_blocks.check_pad(cfg, mix)
    out = []
    for j, n in enumerate(lens):
        total = n - n % B + n_blocks * B
        seq = traffic.token_ids(seed, 900000 + j, total, cfg["vocab_size"])
        passes = []
        for k in range(n_blocks):
            base = n - n % B + k * B
            head = n % B if k == 0 else 0
            undecided = np.arange(B) >= head
            half = undecided & (np.arange(B) >= head + (B - head + 1) // 2)
            for blk_masked in (undecided, half, np.zeros(B, bool)):
                ids = np.zeros((pad,), "int32")
                ids[:base + B] = seq[:base + B]
                masked = np.zeros((pad,), bool)
                masked[base:base + B] = blk_masked
                logits, router = low(params, ids, masked,
                                     np.arange(base, base + B))
                passes.append({
                    "base": base, "tokens": np.asarray(seq[base:base + B]),
                    "masked": blk_masked, "quota": int(blk_masked.sum()),
                    "logits": np.asarray(logits),
                    # [B, L, E] -> [L, B, E], as the program yields them
                    "router_logits": np.transpose(np.asarray(router),
                                                  (1, 0, 2))})
        fine, got = serve_blocks.check_request(
            full, params, B, cell.tolerance, pad, seq[:n], total - n,
            {"tokens": seq[n:], "finish": "length", "passes": passes})
        worst = max(got["denoise"], got["commit"])
        print(f"[bf16 control] seed {seed} prompt {n}: the reference in "
              f"bfloat16 throughout, its router choices offered at near "
              f"ties (at most {got['taken']} taken a pass), is off the float32 "
              f"reference by {worst:.4g} of its range over "
              f"{got['passes']} passes (tolerance {cell.tolerance:.4g}): "
              f"the cell's comparison says "
              f"{'fine' if fine else 'NOT correct'}", flush=True)
        out.append((n, fine, worst))
    return out


def main(argv=None) -> int:
    import harness

    argv = sys.argv[1:] if argv is None else list(argv)
    if "--standin" in argv:
        # 'stated', 'fp8' or this file's own reading through the shared
        # stand-ins, judged by the bfloat16 entry (standins.py)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import standins

        return standins.control(argv, "sdar30b-blockgen")
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sdar30b-blockgen")
    ap.add_argument("--seed", type=int, default=3200000003)
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
