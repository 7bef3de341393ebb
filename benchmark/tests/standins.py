"""Stand-ins for a program that serves in a lower precision: the plain
reference with roundings put in, through the check as the cell's driver
makes it (``serve.reference_check`` and its five siblings).  They fix the
``bfloat16`` entry of a configuration's ``check_tolerance``; no benchmark
run calls them.

* ``stated``: what the configuration's ``as_run.bfloat16`` states.  Every
  weight that ``keeps_float32`` does not list is rounded to bfloat16; the
  residual stream after each sublayer, K and V (or the latent row) as
  written to pages, and the activations that enter a product that is not
  kept are rounded to bfloat16 (the reference's ``_at`` hooks).  Products
  accumulate in float32; norms, softmax, the router's product and scores,
  the convolution rows, the recurrences and their decay stay float32.
* ``throughout``: weights, activations, products and state all bfloat16
  (the control of ``bf16_control*.py`` since PR 28, unchanged).
* ``fp8``: as ``stated`` with the rounded weights and the page rows
  through float8's precision (4 exponent and 3 mantissa bits, as
  ``float8_e4m3fn`` has) under a power-of-two scale an array: the control
  that the ``bfloat16`` limit has to fail.

    python3 benchmark/tests/standins.py --workload CELL [--seeds a,b,c]
        [--standins stated,throughout,fp8] [--entry float32|bfloat16]
        [--margins entry,auto,<number>] [--rehearse]
        [--out chiprun_out/pr67/standins_CELL.json]

prints one line a stand-in, seed and prompt; without ``--rehearse`` it is
the published widths and needs the chip.
"""
import argparse
import fnmatch
import inspect
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (BENCH, os.path.dirname(BENCH))
                if p not in sys.path]

STANDINS = ("stated", "throughout", "fp8")
F8_MAX = 240.0     # the largest finite of an IEEE-like 4-bit exponent, 3-bit
#                    mantissa (float8_e4m3fn reaches 448 with the same 3 bits)


def through(x, kind):
    """``x`` rounded to bfloat16's 8 significant bits (``stated``) or, under
    a power-of-two scale of the whole array, to float8's 4 (``fp8``), as
    float32 values.  ``lax.reduce_precision``, not a pair of ``astype``:
    XLA:TPU may drop a conversion down and up again as excess precision
    (PR 67's first chip readings had ``fp8`` equal ``stated`` to four
    digits), and a reduce-precision it must keep."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    if kind == "fp8":
        top = jnp.max(jnp.abs(x))
        scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.where(top > 0, top, 1.0)
                                           / F8_MAX)))
        x = jax.lax.reduce_precision(x / scale, 4, 3) * scale
    return jax.lax.reduce_precision(x, 8, 7)


def rounder(kind, cfg):
    """The reference's ``ROUND`` under stand-in ``kind``: activations
    through bfloat16; under ``fp8`` the page rows through float8.  Where
    the configuration's ``as_run.bfloat16`` states ``"residual_stream":
    "float32"`` the stream between sublayers (what a router's norm reads)
    is left whole."""
    whole = cfg["as_run"].get("bfloat16", {}).get("residual_stream") \
        == "float32"

    def at(where, x):
        if whole and where == "residual":
            return x
        return through(x, "fp8" if kind == "fp8" and where == "pages"
                       else "stated")
    return at


def weight_names(scope, cfg, kept: bool, prefix="llama."):
    """The scope's floating matrices under ``prefix`` that are no pool and
    no slot state: those the configuration's ``keeps_float32`` patterns
    match (``kept``) or the others."""
    import harness

    patterns = harness.kept_patterns(cfg)
    out = []
    for n in scope.local_var_names():
        short = n[len(prefix):]
        if not n.startswith(prefix) or fnmatch.fnmatchcase(short, "pool_*") \
                or fnmatch.fnmatchcase(short, "*_state_*"):
            continue
        v = scope.find_var(n)
        if getattr(v, "ndim", 0) < 2 or "float" not in str(v.dtype):
            continue
        if kept == any(fnmatch.fnmatchcase(short, p) for p in patterns):
            out.append(n)
    return out


def round_in_place(scope, cfg, kind) -> dict:
    """Every matrix that is not kept, rounded through ``kind`` where it
    lies (float32 values that bfloat16 holds): the stand-in then takes no
    more memory than the reference does.  Returns the matrices as they
    were, on the host, for ``put_back`` (drawing them again would not do:
    a redraw scales each matrix to the sample deviation of what lies
    there, and a new scope's start-up draws differ, either way off the
    first draw by 1e-4 to 1e-3 of a weight)."""
    import functools

    import jax

    one = jax.jit(functools.partial(through, kind=kind), donate_argnums=0)
    were = {}
    for n in weight_names(scope, cfg, kept=False):
        v = scope.find_var(n)
        were[n] = np.asarray(v)
        scope.set_var(n, one(v))
    return were


def put_back(scope, were: dict):
    import jax.numpy as jnp

    for n in list(were):
        scope.set_var(n, jnp.asarray(were.pop(n)))


def lowered(ref, cfg, kind):
    """The stand-in's forward, jitted: ``(params, *inputs, rows) ->
    (logits, router logits or None)`` in float32, ``inputs`` being what
    the reference's ``forward`` takes between the parameters and the
    configuration.  ``stated`` and ``fp8`` run on weights rounded where
    they lie (``round_in_place``) with the reference's hooks set;
    ``throughout`` casts everything."""
    import jax
    import jax.numpy as jnp

    takes = inspect.signature(ref.forward).parameters
    extra = {"keep_router": True} if "keep_router" in takes else {}

    def run(p, *inputs_rows):
        *inputs, rows = inputs_rows
        if kind == "throughout":
            if "dtype" in takes:
                out = ref.forward(p, *inputs, cfg, rows,
                                  dtype=jnp.bfloat16, **extra)
            else:
                p = jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.bfloat16), p)
                out = ref.forward(p, *inputs, cfg, rows, **extra)
        else:
            ref.ROUND = rounder(kind, cfg)
            try:
                out = ref.forward(p, *inputs, cfg, rows, **extra)
            finally:
                ref.ROUND = None
        out = out if isinstance(out, tuple) else (out, None)
        return tuple(None if a is None else a.astype(jnp.float32)
                     for a in out[:2])

    return jax.jit(run)


def _scope(cell, seed):
    """The weights as the cell's driver draws them from the seed."""
    import serve_blocks
    import serve_delta
    import serve_share
    import serve_state

    seeded = {"serve": serve_blocks.seeded_scope,
              "serve_blocks": serve_blocks.seeded_scope,
              "serve_chunks": serve_blocks.seeded_scope,
              "serve_state": serve_state.seeded_scope,
              "serve_delta": serve_delta.seeded_scope,
              "serve_share": serve_share.seeded_scope}
    # (the builder's module itself: ``Cell.builder`` would hold the cell
    # to what this engine runs in, and a sweep holds it to an entry)
    import harness

    return seeded[cell.mix["driver"]](
        harness.load_module("builders", cell.cfg["builder"]), cell.cfg,
        cell.mix, seed)


def _block_passes(cell, seed):
    """``serve_blocks``'s compared requests, teacher-forced
    (``bf16_control_sdar.py``): a prompt, and ``check_blocks`` blocks of
    two denoising passes and a commit pass each (the block all undecided
    behind the prompt's tail, half decided, clean)."""
    import serve_blocks
    import traffic

    cfg, mix = cell.cfg, cell.mix
    B = int(cfg["assumed"]["generation"]["block_length"])
    n_blocks = int(mix["check_blocks"])
    pad = serve_blocks.check_pad(cfg, mix)
    for j, n in enumerate(mix["reference_prompts"]):
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
                passes.append((base, blk_masked, ids, masked))
        yield n, seq, total, passes


def produce(cell, seed, kind, scope) -> list:
    """What stand-in ``kind`` yields for each compared request, on the
    host: the result an engine would have handed the cell's comparison.
    ``stated`` and ``fp8`` round the scope's weights where they lie and
    put them back."""
    import serve
    import serve_state
    import traffic

    cfg, mix = cell.cfg, cell.mix
    ref = cell.reference()
    were = {} if kind == "throughout" \
        else round_in_place(scope, cfg, kind)
    params = None
    try:
        params = ref.params_from_scope(scope, cfg)
        low = lowered(ref, cfg, kind)
        out = []
        if mix["driver"] == "serve_blocks":
            B = int(cfg["assumed"]["generation"]["block_length"])
            for n, seq, total, passes in _block_passes(cell, seed):
                res = []
                for base, blk_masked, ids, masked in passes:
                    logits, router = low(params, ids, masked,
                                         np.arange(base, base + B))
                    res.append({
                        "base": base,
                        "tokens": np.asarray(seq[base:base + B]),
                        "masked": blk_masked,
                        "quota": int(blk_masked.sum()),
                        "logits": np.asarray(logits),
                        # [B, L, E] -> [L, B, E], as the program yields
                        "router_logits": np.transpose(np.asarray(router),
                                                      (1, 0, 2))})
                out.append((n, seq, {"tokens": seq[n:], "finish": "length",
                                     "passes": res}))
            return out
        new = serve.CHECK_NEW_TOKENS
        pad = serve_state.check_pad(mix)
        for j, n in enumerate(mix["reference_prompts"]):
            seq = traffic.token_ids(seed, 900000 + j, n + new - 1,
                                    cfg["vocab_size"])
            ids = np.zeros((pad,), "int32")
            ids[:len(seq)] = seq
            logits, router = low(params, ids,
                                 np.arange(n - 1, n - 1 + new))
            # row n - 1 + k yields token n + k: the tokens a program
            # would have returned are the teacher's, one more at the end
            res = {"tokens": seq[n:] + [1], "finish": "length",
                   "logits": list(np.asarray(logits))}
            if router is not None:
                res["router_logits"] = list(np.asarray(router))
            out.append((n, seq, res))
        return out
    finally:
        del params
        put_back(scope, were)


def compare(cell, seed, kind, scope, produced) -> list:
    """``[{prompt, fine, rel, router_off, near_ties, taken}, ...]``: what
    the cell's own comparison says of ``produced`` against the float32
    reference on the seed's weights, judged by the entry of
    ``check_tolerance`` (and its near-tie margin) that ``cell.cfg`` holds
    now."""
    import serve
    import serve_blocks
    import serve_delta
    import serve_state

    cfg, mix = cell.cfg, cell.mix
    driver = mix["driver"]
    ref = cell.reference()
    params = ref.params_from_scope(scope, cfg)
    tol = cell.tolerance
    margin = cfg["check_tolerance"].get(
        "near_tie_margin_share_of_router_range")
    out = []
    if driver == "serve_blocks":
        B = int(cfg["assumed"]["generation"]["block_length"])
        full = serve_blocks.jitted_forward(ref, cfg)
        pad = serve_blocks.check_pad(cfg, mix)
    elif driver == "serve_delta":
        full, pad = serve_delta.jitted_forward(ref, cfg), \
            serve_state.check_pad(mix)
    elif driver == "serve":
        full, pad = serve.jitted_forward(ref, cfg), \
            serve_state.check_pad(mix)
    else:
        full, pad = serve_state.jitted_forward(ref, cfg), \
            serve_state.check_pad(mix)
    router_tol = cell.router_tolerance
    for n, seq, res in produced:
        if driver == "serve_blocks":
            fine, got = serve_blocks.check_request(
                full, params, B, tol, pad, seq[:n], len(seq) - n, res,
                router_tol=router_tol)
            got["rel"] = max(got["denoise"], got["commit"])
        elif driver == "serve":
            fine, got = serve.check_request(full, params, tol, pad,
                                            seq[:n], res, cfg)
        elif driver == "serve_delta":
            fine, got = serve_delta.check_request(full, params, tol, pad,
                                                  seq[:n], res)
        else:
            fine, got = serve_state.check_request(full, params, tol, pad,
                                                  seq[:n], res, router_tol)
        r = dict(prompt=n, fine=bool(fine), rel=got["rel"],
                 router_off=got.get("router_off"),
                 near_ties=got.get("near_ties"), taken=got.get("taken"))
        print(f"[stand-in {kind}] {cell.config_name} seed {seed} prompt "
              f"{n}: off the float32 reference by {r['rel']:.4g} of its "
              f"range (limit {tol:.4g})"
              + ("" if r["router_off"] is None else
                 f"; router off by {r['router_off']:.3g} of a row's range "
                 f"(limit {router_tol}, margin {margin}), "
                 f"{r['near_ties']} near ties, "
                 f"{r['taken']} taken")
              + f": {'fine' if r['fine'] else 'NOT correct'}", flush=True)
        out.append(r)
    return out


def readings(cell, seed: int, kind: str, scope=None) -> list:
    """``compare`` of what ``produce`` yields: one stand-in on one seed."""
    scope = _scope(cell, seed) if scope is None else scope
    return compare(cell, seed, kind, scope,
                   produce(cell, seed, kind, scope))


def hold_to(cell, dtype: str, margin=None):
    """Judge by the ``dtype`` entry of the configuration's
    ``check_tolerance`` (``margin``: another near-tie margin, for a
    sweep)."""
    if not cell.hold_to(dtype, margin):
        raise SystemExit(f"{cell.config_name} has no {dtype} entry")


PROBE = 0.5         # of a row's range: the margin the deviation is read at


def round_up(x: float) -> float:
    """The next of 1, 1.5, 2, 3, 5, 7.5 x 10^k at or above ``x``."""
    k = 10.0 ** np.floor(np.log10(x))
    return float(f"{next(m * k for m in (1, 1.5, 2, 3, 5, 7.5, 10) if m * k >= x):.3g}")


def control(argv, workload=None) -> int:
    """``bf16_control*.py --standin KIND``: one stand-in on one seed
    through the cell's comparison, judged by an entry of
    ``check_tolerance`` (``bfloat16`` unless ``--entry`` says another).
    As those scripts: 0 where the check fails the stand-in, 1 where it
    passes it."""
    import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=workload, required=not workload)
    ap.add_argument("--seed", type=int, default=6700000003)
    ap.add_argument("--standin", choices=STANDINS, default="throughout")
    ap.add_argument("--entry", default="bfloat16")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    hold_to(cell, args.entry)
    got = readings(cell, args.seed, args.standin)
    failed = [r["prompt"] for r in got if not r["fine"]]
    print(f"[stand-in {args.standin}] not correct on prompts {failed} of "
          f"{[r['prompt'] for r in got]} by the {args.entry} entry: the "
          f"check {'fails' if failed else 'PASSES'} it", flush=True)
    return 0 if failed else 1


def main(argv=None) -> int:
    import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="6700000001,6700000002,6700000003")
    ap.add_argument("--standins", default=",".join(STANDINS))
    ap.add_argument("--entry", default="float32",
                    help="the check_tolerance entry to judge by")
    ap.add_argument("--margins", default="entry",
                    help="near-tie margins: 'entry' (the entry's own), "
                         "'auto' (four times the largest router deviation "
                         "that 'stated' reads on the first seed at a margin "
                         "of 0.5, rounded up) or numbers")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    hold_to(cell, args.entry)
    if not args.rehearse:
        import jax

        from paddle_tpu.compile_cache import ensure_compile_cache

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        ensure_compile_cache()
    routed = "near_tie_margin_share_of_router_range" \
        in cell.cfg["check_tolerance"]
    margins = args.margins.split(",") if routed else ["entry"]
    record = {"workload": cell.name, "config": cell.config_name,
              "entry": args.entry, "rehearse": args.rehearse, "runs": []}
    seeds = [int(s) for s in args.seeds.split(",")]
    kinds = args.standins.split(",")
    auto = None
    for seed in seeds:
        hold_to(cell, args.entry)
        scope = _scope(cell, seed)        # one seed's weights at a time
        made = {kind: produce(cell, seed, kind, scope) for kind in kinds}

        def judged(m, kind):
            hold_to(cell, args.entry, None if m == "entry" else m)
            got = compare(cell, seed, kind, scope, made[kind])
            record["runs"].append({
                "margin": cell.cfg["check_tolerance"].get(
                    "near_tie_margin_share_of_router_range"),
                "seed": seed, "standin": kind, "prompts": got})
            return got

        for m in margins:
            if m == "auto":
                if auto is None:
                    # how far a router that reads a rounded residual lies
                    # off: 'stated' on the first seed, judged at a margin
                    # that takes the program's pick wherever it differs (at
                    # a narrow one a flipped pick moves every later score,
                    # and the deviation read is the flip's, not the
                    # rounding's); four times that, rounded up
                    auto = round_up(4.0 * max(
                        r["router_off"] for r in judged(PROBE, "stated")))
                m = auto
            for kind in kinds:
                judged(m, kind)
        del scope, made
    for kind in args.standins.split(","):
        for margin in sorted({r["margin"] for r in record["runs"]},
                             key=lambda v: v or 0):
            by_seed = [max(p["rel"] for p in r["prompts"])
                       for r in record["runs"]
                       if r["standin"] == kind and r["margin"] == margin]
            off = [p["router_off"] for r in record["runs"]
                   for p in r["prompts"] if r["standin"] == kind
                   and r["margin"] == margin and p["router_off"] is not None]
            if by_seed:
                print(f"[stand-in {kind}] {cell.config_name} margin "
                      f"{margin}: largest reading a seed "
                      f"{[float(f'{v:.4g}') for v in by_seed]}; over all "
                      f"{max(by_seed):.4g}, least of the seeds' largest "
                      f"{min(by_seed):.4g}"
                      + (f"; router off by at most {max(off):.3g}"
                         if off else ""), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
