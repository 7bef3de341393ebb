#!/usr/bin/env python
"""Hash the decode program and two prefill (or chunk) rungs of serving
cells as a tree lowers them on the CPU, at published widths, from shapes
alone.

    python tools/program_hash.py TREE [CELL ...]

Builds, with ``TREE``'s ``paddle_tpu`` and ``TREE``'s builders, the
programs the paged ``GenerationEngine`` would build for each cell (its
configuration and mix as ``TREE/BENCHMARK.json`` and ``TREE/benchmark``
give them), lowers each with ``jax.jit(...).lower`` for this host's CPU
from ``ShapeDtypeStruct`` alone (no weight is made, nothing is compiled or
run) and prints a hash of the StableHLO text.  Two trees build the same
programs where the hashes agree: how a PR that adds a mechanism shows that
the cells sharing its code kept theirs.  The CPU lowering takes the
reference formulations (no Mosaic call), so it says nothing of a kernel's
body: those are compared on the chip.  Default cells: the five that share
code with ``deepseek-v2-docqa`` (PR 56).
"""
import hashlib
import json
import os
import sys

CELLS = ["gigachat35-ragturns", "command-a-plus-ragdocs",
         "solar-open2-agentturns", "smallthinker21b-mixedlen",
         "lfm2-24b-longanswer"]


def main(argv) -> int:
    tree = os.path.abspath(argv[1])
    cells = argv[2:] or CELLS
    sys.path[:0] = [os.path.join(tree, "benchmark"), tree]
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    import harness
    import paddle_tpu as pt
    import importlib

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    if not llama.__file__.startswith(tree + os.sep):
        print(f"{llama.__file__} is not under {tree}", file=sys.stderr)
        return 2
    mesh = dp_mesh(1, devices=jax.devices()[:1])

    def lowered(build, shapes):
        main, startup = pt.Program(), pt.Program()
        startup._is_startup = True
        with pt.program_guard(main, startup):
            out = build()
        feeds, fetches = out[0], out[1]
        names = [fetches[n].name for n in ("next_token", "expert_counts")
                 if n in fetches]
        fn, mut_in, const_in, _ = build_sharded_step(main, feeds, names,
                                                     mesh)
        block = main.global_block()

        def spec(shape, dtype):
            dtype = {"int64": "int32", "float64": "float32"}.get(
                str(dtype), str(dtype))
            return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))

        def state(ns):
            return tuple(spec(block._find_var_recursive(n).shape,
                              block._find_var_recursive(n).dtype)
                         for n in ns)

        text = fn.lower(tuple(spec(*shapes[n]) for n in feeds),
                        state(mut_in), state(const_in),
                        spec((), "int32")).as_text()
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    for name in cells:
        cell = harness.Cell(name)
        cfg, e = cell.cfg, cell.mix["engine"]
        model = cell.builder().model_args(cfg)
        slots, pt_, seq = e["num_slots"], e["page_tokens"], e["max_seq_len"]
        np_slot = seq // pt_
        pages = slots * np_slot + 1
        chunk = int(e.get("prefill_chunk") or 0)
        windows = {lay.get("window") for lay in model.get(
            "layer_pattern") or [] if lay.get("window")}
        wpages = (slots * (max(windows) // pt_ + 1) + 1 + chunk // pt_) \
            if windows else None
        paged = dict(num_pages=pages, page_tokens=pt_,
                     num_window_pages=wpages)
        table = ((slots, np_slot), "int32")
        shapes = {"tokens": ((slots, 1), "int64"),
                  "positions": ((slots,), "int32"), "block_tables": table,
                  "live": ((slots,), "int32"), "block_tables_window": table}
        print(name, "decode", lowered(lambda: llama.build_llama_decode(
            slots, seq, name="llama", **paged, **model), shapes), flush=True)
        rungs = sorted(e["prefill_buckets"])
        one = ((1, np_slot), "int32")
        for b in (rungs[0], rungs[-1]):
            if chunk:
                shapes = {"chunk_ids": ((1, b), "int64"),
                          "base": ((1,), "int32"), "block_table": one,
                          "chunk_len": ((1,), "int32"),
                          "block_table_window": one,
                          "last_off": ((1,), "int64")}
                got = lowered(lambda: llama.build_llama_prefill_chunk(
                    b, seq, pages, pt_, name="llama",
                    num_window_pages=wpages, page_aligned=True, **model),
                    shapes)
            else:
                shapes = {"input_ids": ((1, b), "int64"),
                          "last_pos": ((1,), "int64"), "block_table": one,
                          "prompt_len": ((1,), "int32"),
                          "block_table_window": one,
                          "slot": ((1,), "int32")}
                got = lowered(lambda: llama.build_llama_prefill(
                    1, b, name="llama", cache_slots=slots, max_seq_len=seq,
                    paged=True, **paged, **model), shapes)
            print(name, "chunk" if chunk else "prefill", b, got, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
