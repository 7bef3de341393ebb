"""Compile-only checks at real widths for a described TPU v5e (no chip):
the BERT step on one chip and on a 2x2 mesh, the Mistral decode step and
the widest prefill rung of each serving mix, with ``memory_analysis()``
printed (``pytest -s``).  This is how slots are sized without chip time.

The topology is described inside a fixture, in this one file, because only
one process at a time may load the TPU's library.  Nothing here runs: a
compile that passes is not a chip run.

    python -m pytest benchmark/tests/test_compile_only.py -s
"""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, ROOT) if p not in sys.path]

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HBM_BYTES = 16 * 2 ** 30


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_tpu(monkeypatch):
    """The program asks ``jax.default_backend()`` to choose its attention
    lowering; a described chip is not attached, so the test answers for
    it.  The persistent compile cache is off: a compile for a described
    device is written but can never be read back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu import compile_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "ensure_compile_cache", lambda: None)
    import paddle_tpu.parallel.sharded as sharded
    monkeypatch.setattr(sharded, "ensure_compile_cache", lambda: None)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _compile(program, feed_names, fetch_names, devices, feed_shapes):
    """Lower block 0 of ``program`` for the described ``devices`` (data
    parallel over them) from shapes alone and compile it."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel import build_sharded_step, dp_mesh

    mesh = dp_mesh(len(devices), devices=devices)
    fn, mut_in, const_in, _ = build_sharded_step(
        program, feed_names, fetch_names, mesh)
    block = program.global_block()
    rep = NamedSharding(mesh, P())

    def spec(shape, dtype, sharding):
        dtype = {"int64": "int32", "float64": "float32"}.get(
            str(dtype), str(dtype))
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                    sharding=sharding)

    def state(names):
        out = []
        for n in names:
            v = block._find_var_recursive(n)
            out.append(spec(v.shape, v.dtype, rep))
        return tuple(out)

    dp = NamedSharding(mesh, P("dp")) if len(devices) > 1 else rep
    feeds = tuple(spec(s, d, dp) for s, d in feed_shapes)
    step = spec((), "int32", rep)
    compiled = fn.lower(feeds, state(mut_in), state(const_in), step).compile()
    return compiled


def _report(what, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"\n[compile-only] {what}: arguments "
          f"{m.argument_size_in_bytes / 2**30:.2f} GiB, outputs "
          f"{m.output_size_in_bytes / 2**30:.2f} GiB, aliased "
          f"{m.alias_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{m.temp_size_in_bytes / 2**30:.2f} GiB, in all "
          f"{total / 2**30:.2f} GiB per device")
    return total


@pytest.mark.parametrize("chips,mix_name", [(1, "train-steady"),
                                            (4, "train-steady-dp4")])
def test_bert_step_compiles(topo, as_tpu, chips, mix_name):
    import harness

    cfg = _json("configs", "bert-base-mlm.json")
    builder = harness.load_module("builders", cfg["builder"])
    mix = _json("traffic", mix_name + ".json")
    seq = mix["seq_len"]
    batch = mix["per_chip_batch"] * chips
    pred = builder.max_predictions(cfg, seq)
    main_p, _, feed_names, loss = builder.build(
        cfg, batch, seq, cfg["recipe"]["dropout"])
    shapes = {"input_ids": ((batch, seq), "int64"),
              "token_type_ids": ((batch, seq), "int64"),
              "attn_mask": ((batch, seq), "float32"),
              "mlm_positions": ((batch, pred), "int64"),
              "mlm_labels": ((batch, pred), "int64"),
              "mlm_weights": ((batch, pred), "float32")}
    compiled = _compile(main_p, feed_names, [loss.name],
                        list(topo.devices)[:chips],
                        [shapes[n] for n in feed_names])
    total = _report(f"BERT-base step, {batch} x {seq} on {chips} chip(s)",
                    compiled)
    text = compiled.as_text()
    assert total < HBM_BYTES
    if chips == 1:
        assert "tpu_custom_call" in text, "no Pallas kernel in the step"
    else:
        assert "all-reduce" in text, "no gradient all-reduce in the step"


def _decode_program(cfg, e):
    import paddle_tpu as pt
    from paddle_tpu.models.llama import build_llama_decode

    import harness
    model_args = harness.load_module("builders", cfg["builder"]).model_args

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    pages = e["num_slots"] * (e["max_seq_len"] // e["page_tokens"]) + 1
    with pt.program_guard(main, startup):
        feeds, fetches, _ = build_llama_decode(
            e["num_slots"], e["max_seq_len"], name="llama", paged=True,
            num_pages=pages, page_tokens=e["page_tokens"],
            **model_args(cfg))
    return main, feeds, fetches, pages


def _prefill_program(cfg, e, bucket):
    import paddle_tpu as pt
    from paddle_tpu.models.llama import build_llama_prefill

    import harness
    model_args = harness.load_module("builders", cfg["builder"]).model_args

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    pages = e["num_slots"] * (e["max_seq_len"] // e["page_tokens"]) + 1
    with pt.program_guard(main, startup):
        feeds, fetches = build_llama_prefill(
            1, bucket, name="llama", attn_impl="auto",
            cache_slots=e["num_slots"], max_seq_len=e["max_seq_len"],
            paged=True, num_pages=pages, page_tokens=e["page_tokens"],
            **model_args(cfg))
    return main, feeds, fetches


@pytest.mark.parametrize("mix_name", ["chat-steady", "longprompt-pool"])
def test_mistral_programs_compile_and_fit(topo, as_tpu, mix_name):
    """Weights, the whole KV pool and the step's temporaries fit one
    chip: ``memory_analysis`` counts the pool among the arguments, so one
    program's total is what the process holds while it runs."""
    cfg = _json("configs", "mistral-7b-v0.1.json")
    e = _json("traffic", mix_name + ".json")["engine"]
    one = list(topo.devices)[:1]
    slots, np_slot = e["num_slots"], e["max_seq_len"] // e["page_tokens"]

    main, feeds, fetches, pages = _decode_program(cfg, e)
    shapes = {"tokens": ((slots, 1), "int64"),
              "positions": ((slots,), "int32"),
              "block_tables": ((slots, np_slot), "int32"),
              "live": ((slots,), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"Mistral decode step, {mix_name}: {slots} slots x "
                    f"{e['max_seq_len']}, {pages} pages", compiled)
    assert total < 0.95 * HBM_BYTES

    bucket = max(e["prefill_buckets"])
    main, feeds, fetches = _prefill_program(cfg, e, bucket)
    shapes = {"input_ids": ((1, bucket), "int64"),
              "last_pos": ((1,), "int64"),
              "block_table": ((1, np_slot), "int32"),
              "prompt_len": ((1,), "int32")}
    compiled = _compile(main, feeds, [fetches["next_token"].name], one,
                        [shapes[n] for n in feeds])
    total = _report(f"Mistral paged prefill, {mix_name}: rung {bucket}",
                    compiled)
    assert total < 0.95 * HBM_BYTES
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Pallas kernel in the prefill"
