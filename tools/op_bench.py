"""Per-op benchmark harness (reference operators/benchmark/op_tester.cc
+ tools/check_op_benchmark_result.py).

Config-driven: each entry builds a one-op program, jits it through the
normal executor path, and times it on the current device, waiting for
the last result inside the timed region.

Usage:
    python tools/op_bench.py                      # run, print JSON
    python tools/op_bench.py --out results.json   # save
    python tools/check_op_bench.py results.json   # gate vs baseline

The committed baseline (tools/op_bench_baseline.json) was measured on
TPU v5 lite; the gate only compares results from the same device_kind.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np

WARMUP = 3
ITERS = 20


def spec(op_type, inputs, outputs=None, attrs=None, name=None):
    return {"name": name or op_type, "op": op_type, "inputs": inputs,
            "outputs": outputs or {"Out": 1}, "attrs": attrs or {}}


def _rand(shape, dtype="float32", lo=None, hi=None, seed=0):
    rng = np.random.RandomState(seed)
    if dtype in ("int64", "int32"):
        return rng.randint(lo or 0, hi or 100, shape).astype(dtype)
    x = rng.randn(*shape).astype(dtype)
    if lo is not None:
        x = np.clip(x, lo, hi)
    return x


# the top-50 hot ops of the flagship models (BERT/ResNet/seq2seq):
# matmuls, convs, norms, elementwise chains, reductions, embeddings,
# attention, optimizer update, dropout, losses
B, S, H = 32, 128, 768
CONFIGS = [
    spec("matmul", {"X": _rand((B * S, H)), "Y": _rand((H, H), seed=1)}),
    spec("matmul", {"X": _rand((B * S, H)),
                    "Y": _rand((H, 4 * H), seed=1)}, name="matmul_ffn"),
    spec("mul", {"X": _rand((B, S, H)), "Y": _rand((H, H), seed=1)},
         attrs={"x_num_col_dims": 2, "y_num_col_dims": 1}),
    spec("bmm", {"X": _rand((B * 12, S, 64)),
                 "Y": _rand((B * 12, 64, S), seed=1)}),
    spec("conv2d", {"Input": _rand((B, 64, 56, 56)),
                    "Filter": _rand((64, 64, 3, 3), seed=1)},
         outputs={"Output": 1},
         attrs={"strides": [1, 1], "paddings": [1, 1],
                "dilations": [1, 1], "groups": 1,
                "data_format": "NCHW"}),
    spec("conv3d", {"Input": _rand((4, 16, 8, 28, 28)),
                    "Filter": _rand((32, 16, 3, 3, 3), seed=1)},
         outputs={"Output": 1},
         attrs={"strides": [1, 1, 1], "paddings": [1, 1, 1]}),
    spec("pool2d", {"X": _rand((B, 64, 56, 56))},
         attrs={"pooling_type": "max", "ksize": [2, 2],
                "strides": [2, 2], "paddings": [0, 0]}),
    spec("softmax", {"X": _rand((B * 12, S, S))}),
    spec("log_softmax", {"X": _rand((B * S, 30522 // 4))}),
    spec("layer_norm", {"X": _rand((B, S, H)),
                        "Scale": _rand((H,), seed=1),
                        "Bias": _rand((H,), seed=2)},
         outputs={"Y": 1, "Mean": 1, "Variance": 1},
         attrs={"begin_norm_axis": 2, "epsilon": 1e-5}),
    spec("batch_norm", {"X": _rand((B, 64, 56, 56)),
                        "Scale": _rand((64,), seed=1),
                        "Bias": _rand((64,), seed=2),
                        "Mean": _rand((64,), seed=3),
                        "Variance": np.abs(_rand((64,), seed=4)) + 0.5},
         outputs={"Y": 1, "MeanOut": 1, "VarianceOut": 1,
                  "SavedMean": 1, "SavedVariance": 1},
         attrs={"is_test": True, "epsilon": 1e-5}),
    spec("rms_norm", {"X": _rand((B, S, H)), "Scale": _rand((H,),
                                                            seed=1)},
         outputs={"Y": 1}),
    spec("group_norm", {"X": _rand((B, 64, 28, 28)),
                        "Scale": _rand((64,), seed=1),
                        "Bias": _rand((64,), seed=2)},
         outputs={"Y": 1, "Mean": 1, "Variance": 1},
         attrs={"groups": 8, "epsilon": 1e-5}),
    spec("dropout", {"X": _rand((B, S, H))},
         attrs={"dropout_prob": 0.1,
                "dropout_implementation": "upscale_in_train"}),
    spec("gelu", {"X": _rand((B, S, 4 * H))}),
    spec("relu", {"X": _rand((B, S, 4 * H))}),
    spec("tanh", {"X": _rand((B, S, H))}),
    spec("sigmoid", {"X": _rand((B, S, H))}),
    spec("elementwise_add", {"X": _rand((B, S, H)),
                             "Y": _rand((B, S, H), seed=1)}),
    spec("elementwise_mul", {"X": _rand((B, S, H)),
                             "Y": _rand((B, S, H), seed=1)}),
    spec("elementwise_div", {"X": _rand((B, S, H)),
                             "Y": np.abs(_rand((B, S, H), seed=1)) + 1}),
    spec("elementwise_max", {"X": _rand((B, S, H)),
                             "Y": _rand((B, S, H), seed=1)}),
    spec("reduce_sum", {"X": _rand((B, S, H))}, attrs={"dim": [2]}),
    spec("reduce_mean", {"X": _rand((B, S, H))},
         attrs={"dim": [1, 2]}),
    spec("reduce_max", {"X": _rand((B, S, H))}, attrs={"dim": [2]}),
    spec("lookup_table_v2",
         {"W": _rand((30522, H)),
          "Ids": _rand((B, S), "int64", 0, 30522, seed=1)}),
    spec("transpose2", {"X": _rand((B, S, 12, 64))},
         outputs={"Out": 1, "XShape": 1}, attrs={"axis": [0, 2, 1, 3]}),
    spec("reshape2", {"X": _rand((B, S, H))},
         outputs={"Out": 1, "XShape": 1},
         attrs={"shape": [B * S, H]}),
    spec("concat", {"X": [_rand((B, S, H)), _rand((B, S, H), seed=1)]},
         attrs={"axis": 2}),
    spec("split", {"X": _rand((B, S, H))}, outputs={"Out": 2},
         attrs={"num": 2, "axis": 2, "sections": []}),
    spec("slice", {"Input": _rand((B, S, H))},
         attrs={"axes": [1], "starts": [0], "ends": [64]}),
    spec("gather_nd", {"X": _rand((B, S, H)),
                       "Index": _rand((B, 20, 2), "int64", 0, 32,
                                      seed=1)}),
    spec("top_k", {"X": _rand((B, 30522 // 4))},
         outputs={"Out": 1, "Indices": 1}, attrs={"k": 4}),
    spec("arg_max", {"X": _rand((B * S, 30522 // 4))},
         attrs={"axis": -1}),
    spec("cast", {"X": _rand((B, S, H))},
         attrs={"out_dtype": "bfloat16"}),
    spec("scale", {"X": _rand((B, S, H))},
         attrs={"scale": 2.0, "bias": 1.0}),
    spec("sqrt", {"X": np.abs(_rand((B, S, H))) + 0.1}),
    spec("square", {"X": _rand((B, S, H))}),
    spec("clip", {"X": _rand((B, S, H))},
         attrs={"min": -1.0, "max": 1.0}),
    spec("softmax_with_cross_entropy",
         {"Logits": _rand((B * 20, 30522 // 4)),
          "Label": _rand((B * 20, 1), "int64", 0, 30522 // 4, seed=1)},
         outputs={"Softmax": 1, "Loss": 1}),
    spec("cross_entropy",
         {"X": np.abs(_rand((B * S, 100))) + 0.01,
          "Label": _rand((B * S, 1), "int64", 0, 100, seed=1)},
         outputs={"Y": 1}),
    spec("mean", {"X": _rand((B, S, H))}),
    spec("sum", {"X": [_rand((B, S, H)), _rand((B, S, H), seed=1)]}),
    spec("stack", {"X": [_rand((B, S)), _rand((B, S), seed=1)]},
         outputs={"Y": 1}, attrs={"axis": 0}),
    spec("where", {"Condition": _rand((B, S, H)) > 0,
                   "X": _rand((B, S, H), seed=1),
                   "Y": _rand((B, S, H), seed=2)}),
    spec("flash_attention_qkv", {"QKV": _rand((8, 512, 3 * H))},
         attrs={"num_heads": 12}),
    spec("sgd", {"Param": _rand((H, 4 * H)),
                 "Grad": _rand((H, 4 * H), seed=1),
                 "LearningRate": np.array([0.01], "float32")},
         outputs={"ParamOut": 1}),
    spec("adam",
         {"Param": _rand((H, 4 * H)), "Grad": _rand((H, 4 * H), seed=1),
          "Moment1": _rand((H, 4 * H), seed=2) * 0.01,
          "Moment2": np.abs(_rand((H, 4 * H), seed=3)) * 0.01,
          "LearningRate": np.array([0.001], "float32"),
          "Beta1Pow": np.array([0.9], "float32"),
          "Beta2Pow": np.array([0.999], "float32")},
         outputs={"ParamOut": 1, "Moment1Out": 1, "Moment2Out": 1,
                  "Beta1PowOut": 1, "Beta2PowOut": 1}),
    spec("linear_chain_crf",
         {"Emission": _rand((B, 64, 32)),
          "Transition": _rand((34, 32), seed=1) * 0.1,
          "Label": _rand((B, 64), "int64", 0, 32, seed=2),
          "Length": np.full((B,), 64, "int64")},
         outputs={"LogLikelihood": 1}),
    spec("warpctc",
         {"Logits": _rand((B, 64, 50)),
          "Label": _rand((B, 16), "int64", 1, 50, seed=1),
          "LogitsLength": np.full((B,), 64, "int64"),
          "LabelLength": np.full((B,), 16, "int64")},
         outputs={"Loss": 1}),
]


def bench_one(cfg):
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework.layer_helper import LayerHelper

    main_p, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    feeds = {}
    with pt.program_guard(main_p, startup):
        in_map = {}
        for slot, arr in cfg["inputs"].items():
            arrs = arr if isinstance(arr, list) else [arr]
            vs = []
            for i, a in enumerate(arrs):
                n = f"in_{slot}_{i}"
                v = layers.data(n, list(a.shape), dtype=str(a.dtype),
                                append_batch_size=False)
                feeds[n] = a
                vs.append(v)
            in_map[slot] = vs
        h = LayerHelper(cfg["op"])
        outs = {}
        for slot, k in cfg["outputs"].items():
            outs[slot] = [h.create_variable_for_type_inference("float32")
                          for _ in range(k)]
        h.append_op(cfg["op"], inputs=in_map, outputs=outs,
                    attrs=cfg["attrs"])
    exe = pt.Executor()
    scope = pt.Scope()
    exe.run(startup, scope=scope)
    # stage feeds on device ONCE — re-uploading them every iteration
    # would swamp the op time
    import jax
    feeds = {n: jax.device_put(a) for n, a in feeds.items()}
    fetch = [v for vs in outs.values() for v in vs][:1]

    def fence(r):
        jax.block_until_ready(r[0].value)

    for _ in range(WARMUP):
        r = exe.run(main_p, feed=feeds, fetch_list=fetch, scope=scope,
                    return_numpy=False)
    fence(r)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        r = exe.run(main_p, feed=feeds, fetch_list=fetch, scope=scope,
                    return_numpy=False)
    fence(r)
    dt = (time.perf_counter() - t0) / ITERS
    return dt * 1e6  # us


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--filter", default=None,
                    help="substring filter on config names")
    args = ap.parse_args()
    import jax
    device = jax.devices()[0]
    results = {"device_kind": getattr(device, "device_kind",
                                      str(device)),
               "iters": ITERS, "ops": {}}
    for cfg in CONFIGS:
        if args.filter and args.filter not in cfg["name"]:
            continue
        try:
            us = bench_one(cfg)
            results["ops"][cfg["name"]] = round(us, 1)
            print(f"{cfg['name']:32s} {us:10.1f} us", file=sys.stderr)
        except Exception as e:  # never let one op kill the sweep
            results["ops"][cfg["name"]] = None
            print(f"{cfg['name']:32s} FAIL {type(e).__name__}: "
                  f"{str(e)[:80]}", file=sys.stderr)
    print(json.dumps(results))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
