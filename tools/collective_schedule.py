"""Where does a training cell's compiled step run its collectives?

    python tools/collective_schedule.py <cell> [--dump FILE]

compiles the cell's step as ``benchmark/train.py`` does
(``build_sharded_step`` on a ``dp`` mesh of the cell's chips, then
``fn.lower(...).compile()`` with no arguments) and prints, from the
optimised and scheduled HLO, every collective of the entry computation in
the order the device runs them: its bytes, whether it is a start / done
pair, how many operations (and how many fusions, steps of the collective
that ride on them, and Mosaic calls among them) are scheduled between the
two, and how much of the program lies behind its done.  It runs no cell
and no step: weights and feeds are shapes.  ``--dump`` writes the whole
HLO text.

On a machine with the chips it compiles for them; anywhere else it compiles
for a described v5e 2x2 (the TPU's compiler is installed here), answering
``jax.default_backend()`` with "tpu" so that the program takes the
lowerings it takes on the chip.  A compile that passes is not a chip run:
what the schedule costs is read from a trace
(``collective_exposed_pct.train``, ``collective_exposed_all_pct.train``).

Importing the module changes nothing (``chip_smoke.py`` and the tests
import ``collectives``); the path and the environment are edited under
``main``.  PR 42 made its reading with it (PERF.md section 6); run it again
on a new libtpu before trusting that reading.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(ITEMSIZE) + r")\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def shape_bytes(text: str) -> int:
    """Bytes of every array in a result type (a tuple's parts summed)."""
    total = 0
    for dtype, dims in _ARRAY.findall(text):
        size = ITEMSIZE[dtype]
        for d in filter(None, dims.split(",")):
            size *= int(d)
        total += size
    return total


def entry_instructions(hlo: str) -> list:
    """``(name, opcode, result type, rest of the line)`` of the entry
    computation's instructions, in schedule order (the module the compiler
    returns is scheduled: the text's order is the device's)."""
    out, inside = [], False
    for line in hlo.splitlines():
        if line.startswith("ENTRY "):
            inside = True
            continue
        if not inside:
            continue
        if line.startswith("}"):
            break
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(2), m.group(3)
        op = _OPCODE.search(" " + rest)
        if not op:
            continue
        out.append((name, op.group(1), rest[:op.start()].strip(), rest))
    return out


def _flow(types: dict, result: str, rest: str, op: str) -> str:
    """``f32->bf16``: the element types a collective takes and yields."""
    args = rest[rest.index(op + "(") + len(op) + 1:].split(")")[0]
    taken = {d for a in re.findall(r"%([\w.\-]+)", args)
             for d, _ in _ARRAY.findall(types.get(a, ""))}
    given = {d for d, _ in _ARRAY.findall(result)}
    return f"{'/'.join(sorted(taken))}->{'/'.join(sorted(given))}"


def called_collectives(hlo: str) -> dict:
    """Computation name -> ``(opcode, bytes, channel, flow)`` of each
    collective its body holds, with the key ``"role"`` of an asynchronous
    collective fusion's two ends: XLA:TPU keeps an asynchronous all-reduce
    inside fusions, a start (``AsyncCollectiveStart``), steps that ride on
    compute fusions, and a done (``AsyncCollectiveDone``).  It has no
    ``all-reduce-start`` / ``-done`` instructions (PERF.md section 7)."""
    found, current, types = {}, None, {}
    for line in hlo.splitlines():
        head = re.match(r"^%?([\w.\-]+)\s.*\{\s*$", line)
        if head and not line.startswith(" "):
            current, types = head.group(1), {}
            continue
        if current and line.startswith("}"):
            current = None
            continue
        if not current:
            continue
        for role in ("Start", "Done"):
            if f'custom_call_target="AsyncCollective{role}"' in line:
                found.setdefault(current, {"ops": []})["role"] = role.lower()
        m = _INSTR.match(line)
        op = m and _OPCODE.search(" " + m.group(3))
        if not op:
            continue
        result = m.group(3)[:op.start()]
        types[m.group(2)] = result
        if COLLECTIVE.match(op.group(1)):
            channel = re.search(r"channel_id=(\d+)", line)
            found.setdefault(current, {"ops": []})["ops"].append(
                (op.group(1), shape_bytes(result),
                 channel.group(1) if channel else None,
                 _flow(types, result, m.group(3), op.group(1))))
    return found


def _kind(op: str, rest: str) -> str:
    if op == "fusion":
        return "fusion"
    if op == "custom-call" and "tpu_custom_call" in rest:
        return "mosaic"
    return "other"


def collectives(hlo: str) -> list:
    """One dict a collective of the entry computation: ``name``, ``op``,
    ``bytes`` (of what the collective yields), ``flow`` (the element types
    it takes and yields), ``at`` (its place, or its start's, in the
    schedule), ``pair`` (has a start and a done), ``steps`` (compute fusions
    between the two that carry a step of it), ``between`` (operations
    scheduled between the two, by kind), ``behind`` (operations after it, or
    after its done) and ``of`` (the schedule's length)."""
    instrs = entry_instructions(hlo)
    inner = called_collectives(hlo)
    n = len(instrs)
    types = {name: result for name, _, result, _ in instrs}
    kinds = [_kind(op, rest) for _, op, _, rest in instrs]

    def called(i):
        m = re.search(r"calls=%?([\w.\-]+)", instrs[i][3])
        return inner.get(m.group(1)) if m and instrs[i][1] == "fusion" else None

    def channels(i):
        return {c for _, _, c, _ in called(i)["ops"]}

    def row(name, op, nbytes, flow, i, done=None, steps=0):
        between = {}
        for k in kinds[i + 1:done or i]:
            between[k] = between.get(k, 0) + 1
        return {"name": name, "op": op, "bytes": nbytes, "flow": flow,
                "at": i, "pair": done is not None, "steps": steps,
                "between": between, "behind": n - 1 - (done or i), "of": n}

    out, part_of_a_pair = [], set()
    for i in range(n):
        if (called(i) or {}).get("role") != "done":
            continue
        # its start is the fusion that opened the same channel; the compute
        # fusions between them that hold a piece of it are steps
        start = next((j for j in range(i - 1, -1, -1)
                      if (called(j) or {}).get("role") == "start"
                      and channels(i) & channels(j)), None)
        if start is not None:
            steps = [j for j in range(start + 1, i)
                     if called(j) and "role" not in called(j)
                     and channels(i) & channels(j)]
            part_of_a_pair.update([start] + steps)
            kind, nbytes, _, flow = called(start)["ops"][0]
            out.append(row(instrs[start][0], kind, nbytes, flow, start, i,
                           len(steps)))
        part_of_a_pair.add(i)
    for i, (name, op, result, rest) in enumerate(instrs):
        fused = called(i)
        if COLLECTIVE.match(op):
            out.append(row(name, op, shape_bytes(result),
                           _flow(types, result, rest, op), i))
        elif fused and fused["ops"] and i not in part_of_a_pair:
            out.append(row(name, "fused " + fused["ops"][0][0],
                           sum(b for _, b, _, _ in fused["ops"]),
                           fused["ops"][0][3], i))
    return sorted(out, key=lambda r: r["at"])


def cell_step(cell_name: str):
    """``(fn, args, where)`` of the training cell ``cell_name``: its step as
    ``build_sharded_step`` builds it over a mesh of the cell's chips
    (attached, or a described v5e's off the chip), the shapes it is lowered
    at, and a word for the devices.  Called from a tool's ``main`` only: it
    edits the path for the cell's builder and, off the chip, jax's answer
    to ``default_backend``."""
    def load(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    entry = [w for w in load("BENCHMARK.json")["workloads"]
             if w["name"] == cell_name]
    if not entry:
        raise SystemExit(f"no workload {cell_name!r} in BENCHMARK.json")
    cfg = load("benchmark", "configs", entry[0]["config"] + ".json")
    mix = load("benchmark", "traffic", entry[0]["traffic"] + ".json")
    if mix.get("driver") != "train":
        raise SystemExit(f"{cell_name} is not a training cell")
    chips = int(entry[0]["chips"])

    # the cell's builder imports its neighbours under benchmark/ by name
    sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib.util
    import numpy as np
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = importlib.util.spec_from_file_location(
        "cell_builder", os.path.join(BENCH, "builders", cfg["builder"] + ".py"))
    builder = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(builder)

    if jax.devices()[0].platform == "tpu":
        devices = jax.devices()[:chips]
        where = f"{len(devices)} attached {devices[0].device_kind}"
    else:
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache as cc
        devices = list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)[:chips]
        where = f"{len(devices)} described {devices[0].device_kind}"
        # the program asks the backend to choose its lowerings; a compile
        # for a described device cannot be read back from the cache
        jax.default_backend = lambda: "tpu"
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
    if len(devices) < chips:
        raise SystemExit(f"{cell_name} needs {chips} chips, found {len(devices)}")

    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    seq, batch = int(mix["seq_len"]), int(mix["per_chip_batch"]) * chips
    main_p, _, feed_names, loss = builder.build(
        cfg, batch, seq, cfg["recipe"]["dropout"])
    mesh = dp_mesh(chips, devices=devices)
    fn, mut_in, const_in, _ = build_sharded_step(
        main_p, feed_names, [loss.name], mesh)
    block = main_p.global_block()
    rep = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("dp"))

    def shaped(shape, dtype, sharding):
        dtype = {"int64": "int32", "float64": "float32"}.get(
            str(dtype), str(dtype))
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                    sharding=sharding)

    def state(names):
        vs = [block._find_var_recursive(k) for k in names]
        return tuple(shaped(v.shape, v.dtype, rep) for v in vs)

    host = builder.host_batches(0, cfg, batch, seq, 1)[0]
    feeds = tuple(shaped(host[k].shape, host[k].dtype, dp)
                  for k in feed_names)
    return fn, (feeds, state(mut_in), state(const_in),
                shaped((), "int32", rep)), where


def compile_cell(cell_name: str):
    """``(compiled step, where it was compiled for)`` of ``cell_name``, from
    shapes alone."""
    fn, args, where = cell_step(cell_name)
    return fn.lower(*args).compile(), where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    compiled, where = compile_cell(args.cell)
    hlo = compiled.as_text()
    if args.dump:
        os.makedirs(os.path.dirname(os.path.abspath(args.dump)), exist_ok=True)
        with open(args.dump, "w") as f:
            f.write(hlo)
    rows = collectives(hlo)
    m = compiled.memory_analysis()
    print(f"{args.cell}: compiled for {where}; temporaries "
          f"{m.temp_size_in_bytes / 2**30:.3f} GiB a device")
    print(f"{'at':>6} {'behind':>6}  {'MB':>8}  pair  between"
          f"{'':33}  collective")
    for r in rows:
        b = r["between"]
        between = (f"{sum(b.values())} ops: {b.get('fusion', 0)} fusions "
                   f"({r['steps']} steps), {b.get('mosaic', 0)} mosaic"
                   if r["pair"] else "-")
        print(f"{r['at']:>6} {r['behind']:>6}  {r['bytes'] / 1e6:>8.2f}  "
              f"{'yes' if r['pair'] else 'no ':<4}  {between:<40}  "
              f"{r['op']} {r['name']} {r['flow']}")
    sync = [r for r in rows if not r["pair"]]
    print(f"{len(rows)} collectives, {sum(r['bytes'] for r in rows) / 1e6:.1f}"
          f" MB; {len(sync)} synchronous holding "
          f"{sum(r['bytes'] for r in sync) / 1e6:.1f} MB; schedule of "
          f"{rows[0]['of'] if rows else 0} operations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
