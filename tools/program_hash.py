#!/usr/bin/env python
"""Hash the decode program and every prefill (or chunk) rung of serving
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

    python tools/program_hash.py --compiled TREE [CELL ...]

(PR 60) compiles instead, for a described v5e and with the backend answered
for, so the Pallas kernels are in: the decode program and the widest rung
of each cell as the ``Executor`` builds them (``Executor._build``'s jitted
step), once as ``fn.lower(...).compile()`` and once through the program
store's round trip (``jax.export``, serialise, deserialise,
``program_store.wrapped(...).lower(...).compile()``), and prints a hash of
each executable's optimised HLO (``as_text()`` less its ``metadata`` and
with every value and computation named by where it first appears; a
second hash is of its lines sorted and no operand named, for two schedules
of the same operations) with its ``cost_analysis`` flops,
``memory_analysis`` bytes and the seconds ``lower`` and ``compile`` took
(PR 64; on this host's shared cores, so two trees are compared in one
sitting): the stored module compiles to the executable the
step compiled to where the two lines of a program agree.  A minute or two a
program; no chip, nothing runs.  Default cells: ``mistral7b-chat``,
``smallthinker21b-mixedlen``, ``olmo-hybrid7b-longdoc``.  A training cell (PR 62: ``bert-base-seq512``,
``bert-base-seq512-dp4``) gives its one step as ``build_sharded_step``
builds it over a mesh of the cell's chips: the step's own ``jit`` beside the
stored call under the same shardings, donation and compiler options.
``--dump DIR`` (after ``--compiled``) also writes every executable's text as
it is hashed, ``DIR/<n>.txt`` in the order of the lines printed, for a diff.
``--rungs R,R`` (PR 65; after ``--compiled`` and ``--dump``) compiles those
of each cell's rungs that it has instead of the widest, the step's own
``jit`` alone and no decode program: what a PR that changes some rungs
compares on two trees (temporaries, seconds of ``lower`` + ``compile``).
"""
import hashlib
import json
import os
import re
import sys
import time

CELLS = ["gigachat35-ragturns", "command-a-plus-ragdocs",
         "solar-open2-agentturns", "smallthinker21b-mixedlen",
         "lfm2-24b-longanswer"]
COMPILED_CELLS = ["mistral7b-chat", "smallthinker21b-mixedlen",
                  "olmo-hybrid7b-longdoc"]


def main(argv) -> int:
    compiled = argv[1:2] == ["--compiled"]
    if compiled:
        del argv[1]
    dump = None
    if argv[1:2] == ["--dump"]:
        dump = os.path.abspath(argv[2])
        os.makedirs(dump, exist_ok=True)
        del argv[1:3]
    dumped = []
    only = None
    if argv[1:2] == ["--rungs"]:
        only = {int(r) for r in argv[2].split(",")}
        del argv[1:3]
    tree = os.path.abspath(argv[1])
    cells = argv[2:] or (COMPILED_CELLS if compiled else CELLS)
    sys.path[:0] = [os.path.join(tree, "benchmark"), tree]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np

    import harness
    import paddle_tpu as pt
    import importlib.util

    # (``paddle_tpu.models`` exports a function of the module's name)
    llama = importlib.import_module("paddle_tpu.models.llama")
    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    if not llama.__file__.startswith(tree + os.sep):
        print(f"{llama.__file__} is not under {tree}", file=sys.stderr)
        return 2
    mesh = dp_mesh(1, devices=jax.devices()[:1])
    chip = None
    if compiled:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        # (the ops ask the backend which formulation to lower)
        jax.default_backend = lambda: "tpu"

    def canonical(text):
        """An executable's text less what names it and does not make it:
        ``metadata``, the tables of files and frames above the module, and
        every name: a computation is called by where it first appears in
        the module, a value by where it first appears in its computation,
        a computation's parameters by their place.  (XLA names what the
        partitioner makes after the jax primitive an operation came from,
        which is ``stored_step`` for all of a stored call, and jit names
        the entry's parameters after the Python signature.)"""
        lines, table, comps, local = [], False, {}, {}

        def named(match):
            name = match.group(0)
            if name in comps and name not in local:
                return comps[name]
            return local.setdefault(name, "%%v%d" % len(local))

        for line in re.sub(r", metadata=\{[^}]*\}", "",
                           text).splitlines():
            if line in ("FileNames", "FunctionNames", "FileLocations",
                        "StackFrames"):
                table = True
            elif table:
                table = bool(line.strip())
            else:
                head = re.match(r"(ENTRY )?(%[\w.\-]+) \(", line)
                if head:
                    local = {}
                    comps.setdefault(head.group(2), "%%c%d" % len(comps))
                    place = iter(range(len(line)))
                    line = re.sub(r"[\w.\-]+(?=: )",
                                  lambda m: "p%d" % next(place), line)
                lines.append(re.sub(r"%[\w.\-]+", named, line))
        return "\n".join(lines)

    def executable(fn, args):
        """An executable's line (its optimised HLO's hash, flops, bytes) and
        the seconds its ``lower`` and ``compile`` took."""
        t0 = time.monotonic()
        exe = fn.lower(*args).compile()
        took = time.monotonic() - t0
        text = canonical(exe.as_text())
        if dump:
            dumped.append(os.path.join(dump, "%d.txt" % len(dumped)))
            with open(dumped[-1], "w") as f:
                f.write(exe.as_text())
        cost, mem = exe.cost_analysis(), exe.memory_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        # (for two schedules of the same operations: no operand's name)
        lines = "\n".join(sorted(
            re.sub(r"%[\w.\-]+", "%", text).splitlines()))
        return "%s %s flops %.6g bytes %.6g args %d out %d temp %d alias %d" % (
            hashlib.sha256(text.encode()).hexdigest()[:16],
            hashlib.sha256(lines.encode()).hexdigest()[:16],
            cost.get("flops", 0.0), cost.get("bytes accessed", 0.0),
            mem.argument_size_in_bytes, mem.output_size_in_bytes,
            mem.temp_size_in_bytes, mem.alias_size_in_bytes), took

    def lowered(build, shapes):
        main, startup = pt.Program(), pt.Program()
        startup._is_startup = True
        with pt.program_guard(main, startup):
            out = build()
        feeds, fetches = out[0], out[1]
        names = [fetches[n].name for n in ("next_token", "tokens",
                                           "rows_written", "expert_counts")
                 if n in fetches]
        block = main.global_block()

        def spec(shape, dtype):
            dtype = {"int64": "int32", "float64": "float32"}.get(
                str(dtype), str(dtype))
            # (jax.numpy.dtype: numpy alone does not know "bfloat16")
            return jax.ShapeDtypeStruct(tuple(shape), jax.numpy.dtype(dtype),
                                        sharding=chip)

        def state(ns):
            return tuple(spec(block._find_var_recursive(n).shape,
                              block._find_var_recursive(n).dtype)
                         for n in ns)

        if compiled:
            from paddle_tpu.framework import executor

            entry = pt.Executor()._build(main, block, list(feeds), names)
            args = (tuple(spec(*shapes[n]) for n in feeds),
                    state(entry.mut_in), state(entry.const_in),
                    spec((), "int32"))
            return round_trip(entry.fn, args, executor._DONATED)
        fn, mut_in, const_in, _ = build_sharded_step(main, feeds, names,
                                                     mesh)
        text = fn.lower(tuple(spec(*shapes[n]) for n in feeds),
                        state(mut_in), state(const_in),
                        spec((), "int32")).as_text()
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def round_trip(jitted, args, donate_argnums, **jit_kwargs):
        """``jitted``'s executable beside the one of its module after the
        store's round trip, and whether they agree."""
        from jax import export

        from paddle_tpu import program_store

        direct, direct_s = executable(jitted, args)
        if only:
            return "direct %s in %.1f s" % (direct, direct_s)
        blob = program_store.export_step(jitted, args, ("tpu",)).serialize()
        stored, stored_s = executable(program_store.wrapped(
            export.deserialize(blob), donate_argnums, **jit_kwargs), args)
        same = "same" if direct == stored else (
            "same operations, some scheduled in another order"
            if direct.split()[1:] == stored.split()[1:] else "DIFFERENT")
        return ("%s\n    direct %s in %.1f s\n    stored %s in %.1f s "
                "(module %d bytes)") % (same, direct, direct_s, stored,
                                        stored_s, len(blob))

    def train_step(name):
        """The training cell's step over a mesh of its described chips, as
        ``TREE/tools/collective_schedule.py`` builds it."""
        spec = importlib.util.spec_from_file_location(
            "collective_schedule",
            os.path.join(tree, "tools", "collective_schedule.py"))
        schedule = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(schedule)
        fn, args, _ = schedule.cell_step(name)
        return round_trip(fn.jitted, args, fn.donate_argnums,
                          **fn.jit_kwargs)

    for name in cells:
        cell = harness.Cell(name)
        if cell.mix.get("driver") == "train":
            if not compiled:
                print(f"{name}: a training cell, compared with --compiled",
                      file=sys.stderr)
                return 2
            print(name, "step", train_step(name), flush=True)
            continue
        cfg, e = cell.cfg, cell.mix["engine"]
        model = cell.builder().model_args(cfg)
        # (PR 68) the dtype the tree's engine would declare the programs
        # in, where the tree has a rule (``GenerationEngine._dtype_args``)
        rule = getattr(llama, "serving_dtype", None)
        if rule is not None and rule(model) != "float32":
            model["dtype"] = rule(model)
        # (block diffusion: what ``GenerationEngine._blk_args`` hands on)
        bd = model.pop("block_diffusion", None)
        blk = {"block": bd["block"], "mask_id": bd["mask_id"]} if bd else {}
        mask = {"mask_block": bd["block"]} if bd else {}
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
        rows = bd["block"] if bd else 1
        shapes = {"tokens": ((slots, rows), "int64"),
                  "positions": ((slots,), "int32"), "block_tables": table,
                  "live": ((slots,), "int32"), "block_tables_window": table,
                  "masked": ((slots, rows), "int32"),
                  "quota": ((slots,), "int32"), "fresh": ((slots,), "int32")}
        if not only:
            print(name, "decode", lowered(lambda: llama.build_llama_decode(
                slots, seq, name="llama", **paged, **blk, **model), shapes),
                flush=True)
        rungs = sorted(e["prefill_buckets"])
        one = ((1, np_slot), "int32")
        for b in [r for r in rungs if r in only] if only \
                else rungs[-1:] if compiled else rungs:
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
                    paged=True, **paged, **mask, **model), shapes)
            print(name, "chunk" if chunk else "prefill", b, got, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
