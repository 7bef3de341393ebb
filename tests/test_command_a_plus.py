"""``command-a-plus-05-2026`` at a small size (PR 51): a parallel attention
+ FFN block under one LayerNorm, 16 query heads a KV head in window RoPE
layers three to one beside full NoPE layers, a share of sigmoid-routed
experts beside four averaged shared experts, and the first chunked
prefill over two page kinds.

* **The chunk attention** (``ops/pallas/flash_attention.py``
  ``chunk_attention``, interpret mode) against ``_attend_cache`` at
  ``base`` 0, mid-prompt and past the window, 16 query heads a KV head.
* **The block** (``models/llama.py``): ``norm="parallel"`` under a
  LayerNorm against the benchmark's plain reference, and differing from
  ``"pre"``; the fused shared SwiGLU of four times the width at a quarter
  against four averaged; interleaved RoPE at a decode step's position
  bit-equal to the chunk's row there.
* **The share** (``parallel/moe.py`` as it stood): 16 shares of a 128-wide
  router at toy widths, the shared mean counted once, add up to the uncut
  reference layer.
* **The engine** (``serving/generation.py``), in
  ``tests/test_command_a_plus_engine.py`` (a file of its own, so that the
  suite's workers share the two): chunked prefill against the reference,
  chunked against single-shot, window pages, the pool, the refusals.
"""
import importlib.util
import os
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
WINDOW = 32
TOL = 2.0 ** -10          # of the logits' range; float32 reads 1e-6 here


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "cmda_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "command-a-plus-05-2026")
BUILDER = _load("builders", "command_a_plus_engine")


def _cfg(**over):
    """The published keys at a toy size: hidden 64, 32 query over 2 KV
    heads of 16 (16 a KV head, as published), window 32, three sliding
    layers and a full one; a router of 16 experts, 3 a token, of which
    experts 4..7 are held, beside four shared experts of width 32."""
    cfg = {"model_type": "cohere2_moe", "hidden_size": 64,
           "num_hidden_layers": 4, "num_attention_heads": 32, "head_dim": 16,
           "num_key_value_heads": 2, "vocab_size": 97,
           "intermediate_size": 32, "layer_norm_eps": 1e-5,
           "rms_norm_eps": None, "tie_word_embeddings": True,
           "logit_scale": 1, "first_k_dense_replace": 0,
           "use_qk_norm": False, "attention_bias": False,
           "use_parallel_block": True,
           "position_embedding_type": "rope_gptj", "rotary_pct": 1,
           "rope_theta": 50000, "sliding_window": WINDOW,
           "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
           "num_experts": 4, "num_experts_per_tok": 3,
           "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
           "use_gated_activation": True, "hidden_act": "silu",
           "num_shared_experts": 4,
           "shared_expert_combination_strategy": "average",
           "expert_share": {"router_experts": 16, "first": 4},
           "as_run": {"attention_precision": "highest"},
           "assumed": {"eos_id": -1},
           "check_tolerance": {
               "near_tie_margin_share_of_router_range": 4e-4}}
    cfg.update(over)
    return cfg


def _engine(cfg=None, **kw):
    from paddle_tpu.serving import GenerationEngine

    args = dict(num_slots=3, max_seq_len=128,
                prefill_buckets=[8, 16, 32, 64, 128], page_tokens=PAGE,
                attn_impl="xla", keep_logits=True, prefill_chunk=16,
                prefix_reuse=False, speculate=False, eos_id=-1,
                deadline_ms=600000)
    args.update(kw)
    return GenerationEngine(BUILDER.model_args(cfg or _cfg()), **args)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 97, n).tolist()


def _off_reference(eng, cfg, prompt, res):
    """How far a result's logits lie off the reference's single forward
    over prompt plus generated tokens, as a share of its range."""
    n, new = len(prompt), len(res["tokens"])
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    seq = np.asarray(prompt + res["tokens"], "int32")
    want = np.asarray(REF.forward(params, seq, cfg,
                                  np.arange(n - 1, n - 1 + new)))
    got = np.stack(res["logits"])
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


def _rel(got, want):
    return float(np.abs(np.asarray(got) - np.asarray(want)).max()
                 / np.abs(np.asarray(want)).max())


# ---------------------------------------------------------------------------
# the chunk attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("base,window", [
    (0, None), (0, 96), (256, None), (256, 96), (384, 96), (320, 300)])
@pytest.mark.parametrize("rows", [128, 64])
def test_chunk_kernel_is_the_einsum_at_any_base(base, window, rows):
    """``rows`` query rows of 32 heads over 2 KV heads (16 a KV head) at
    ``base`` of a 512-column view: at the start, mid-prompt, and with
    the window's left edge past column 0, inside a key block and on its
    border.  Columns right of the chunk hold a large number that no row
    may read."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.decode_ops import _attend_cache
    from paddle_tpu.ops.pallas.flash_attention import chunk_attention

    rng = np.random.default_rng(base + rows)
    q = jnp.asarray(rng.standard_normal((1, 32, rows, 128)), jnp.float32)
    k = rng.standard_normal((1, 2, 512, 128)).astype("float32")
    v = rng.standard_normal((1, 2, 512, 128)).astype("float32")
    k[:, :, base + rows:] = 1e4
    v[:, :, base + rows:] = 1e4
    pos = jnp.asarray([base], jnp.int32)
    got = chunk_attention(q, jnp.asarray(k), jnp.asarray(v), pos,
                          window=window, interpret=True, block_q=64,
                          block_k=128)
    with jax.default_matmul_precision("highest"):
        want = _attend_cache(q, jnp.asarray(k), jnp.asarray(v), pos, None,
                             window)
    assert _rel(got, want) < 1e-5


def test_chunk_op_is_cached_attention_off_the_chip():
    """The op books its reference formulation on the CPU and gives
    ``cached_attention``'s bits; GQA by index, the window as the op's
    attribute."""
    before = {k: stat_get("attention_lowered_chunk_" + k)
              for k in ("pallas", "reference")}
    rng = np.random.default_rng(5)
    feed = {"q": rng.standard_normal((1, 32, 16, 16)).astype("float32"),
            "k": rng.standard_normal((1, 2, 64, 16)).astype("float32"),
            "v": rng.standard_normal((1, 2, 64, 16)).astype("float32"),
            "pos": np.asarray([24], "int32")}
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [1, 32, 16, 16], append_batch_size=False)
        k = layers.data("k", [1, 2, 64, 16], append_batch_size=False)
        v = layers.data("v", [1, 2, 64, 16], append_batch_size=False)
        pos = layers.data("pos", [1], dtype="int32", append_batch_size=False)
        outs = [layers.chunk_attention(q, k, v, pos, window=20),
                layers.cached_attention(q, k, v, pos, window=20),
                layers.chunk_attention(q, k, v, pos)]
    a, b, c = pt.Executor().run(main, feed=feed, fetch_list=outs)
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert _rel(c, a) > 1e-3             # the window is not inert
    after = {k: stat_get("attention_lowered_chunk_" + k) for k in before}
    assert after == {"pallas": before["pallas"],
                     "reference": before["reference"] + 2}


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def _forward_logits(model, ids, scope):
    from paddle_tpu.models.llama import build_llama_forward

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    startup.random_seed = main.random_seed = 3
    with pt.program_guard(main, startup):
        _, fetches = build_llama_forward(1, len(ids), name="llama",
                                         attn_impl="xla", **model)
    exe = pt.Executor()
    if scope.find_var("llama.embed") is None:
        exe.run(startup, scope=scope)
    out, = exe.run(main, feed={"input_ids": np.asarray([ids], "int64")},
                   fetch_list=[fetches["logits"]], scope=scope)
    return np.asarray(out)[0]


def test_parallel_block_under_a_layer_norm_is_the_reference():
    """The uncached full forward, every row: one LayerNorm a layer, both
    halves reading it, the LayerNorm as the final norm, the tied head.
    The same weights under ``norm="pre"`` (given the second norm it
    reads) or under an RMS norm give other logits."""
    cfg = _cfg()
    model = BUILDER.model_args(cfg)
    ids = _prompt(1, 70)
    scope = pt.Scope()
    got = _forward_logits(model, ids, scope)
    # a LayerNorm weight that is not all ones, so that it is read
    for i in range(4):
        scope.set_var(f"llama.blk{i}.ln1", np.linspace(
            0.5, 1.5, 64).astype("float32"))
    got = _forward_logits(model, ids, scope)
    params = REF.params_from_scope(scope, cfg, "llama")
    want = np.asarray(REF.forward(params, np.asarray(ids, "int32"), cfg))
    assert got.shape == want.shape and _rel(got, want) < TOL
    block = [n for n in scope.local_var_names() if ".blk0." in n]
    assert "llama.blk0.ln1" in block and "llama.blk0.ln2" not in block
    rms = _forward_logits(dict(model, norm_kind="rms"), ids, scope)
    assert _rel(rms, want) > 1e-2
    for i in range(4):                   # the second norm "pre" reads
        scope.set_var(f"llama.blk{i}.ln2", np.ones(64, "float32"))
    pre = _forward_logits(dict(model, norm="pre"), ids, scope)
    assert _rel(pre, want) > 1e-2


def test_norm_layouts_that_do_not_exist_are_refused():
    from paddle_tpu.models.llama import _norm, _norm_modes

    assert _norm_modes("parallel") == (True, False)
    with pytest.raises(ValueError, match="parallel"):
        _norm_modes("sideways")
    x = layers.data("x", [1, 4, 8], append_batch_size=False)
    with pytest.raises(ValueError, match="norm_kind"):
        _norm(x, 1e-5, None, "batch")


def test_fused_shared_swiglu_at_a_quarter_is_four_averaged():
    """What the program keeps (one SwiGLU of 4 x width whose output is
    multiplied by 0.25) against what the reference computes (four SwiGLUs
    one by one, averaged), on the same fused matrices; the sum is not the
    mean."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    hid, width, n = 48, 24, 4
    h = jnp.asarray(rng.standard_normal((19, hid)), jnp.float32)
    gate_up = jnp.asarray(rng.standard_normal((hid, 2 * n * width))
                          * hid ** -0.5, jnp.float32)
    down = jnp.asarray(rng.standard_normal((n * width, hid))
                       * width ** -0.5, jnp.float32)
    with jax.default_matmul_precision("highest"):
        fused = REF.swiglu(h, gate_up, down)
        mean = REF.shared_mean(h, gate_up, down, n)
        one = REF.swiglu(h, jnp.concatenate(
            [gate_up[:, :width], gate_up[:, n * width:][:, :width]], 1),
            down[:width])
    assert _rel(0.25 * fused, mean) < 1e-5
    assert _rel(fused, mean) > 1.0
    assert _rel(one, mean) > 0.1          # and no single expert is it


def test_interleaved_rope_at_a_decode_position_is_the_chunks_row():
    """Row ``t`` of a chunk at ``base`` and a decode step at position
    ``base + t`` rotate the same vector to the same bits, pairs (2i, 2i +
    1); the rotate-half layout gives other numbers."""
    rng = np.random.default_rng(4)
    x = rng.standard_normal((1, 3, 8, 16)).astype("float32")
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xs = layers.data("x", [1, 3, 8, 16], append_batch_size=False)
        one = layers.data("one", [1, 3, 1, 16], append_batch_size=False)
        base = layers.data("base", [1], dtype="int32",
                           append_batch_size=False)
        at = layers.data("at", [1], dtype="int32", append_batch_size=False)
        outs = [layers.rope(xs, base=50000.0, offset=base, interleave=True),
                layers.rope(one, base=50000.0, offset=at, interleave=True),
                layers.rope(xs, base=50000.0, offset=base)]
    chunk, step, half = pt.Executor().run(
        main, feed={"x": x, "one": x[:, :, 5:6],
                    "base": np.asarray([40], "int32"),
                    "at": np.asarray([45], "int32")}, fetch_list=outs)
    assert np.asarray(chunk)[:, :, 5:6].tobytes() == np.asarray(step).tobytes()
    assert _rel(half, chunk) > 1e-2
    want = np.asarray(REF.rope_interleaved(x[0], 50000.0, 40))
    assert _rel(np.asarray(chunk)[0], want) < 1e-6


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

E_ROUTER, SHARES, TOP_K, HID, WIDTH, N_SHARED = 128, 16, 8, 32, 16, 4


def _layer(seed, n=48):
    """One uncut expert layer at toy widths behind a 128-wide router, four
    shared experts fused as the program keeps them, and ``n`` rows."""
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return (scale * rng.normal(size=shape)).astype("float32")

    p = {"router": draw(HID, E_ROUTER, scale=HID ** -0.5),
         "gate_up": draw(E_ROUTER, HID, 2 * WIDTH, scale=HID ** -0.5),
         "down": draw(E_ROUTER, WIDTH, HID, scale=WIDTH ** -0.5),
         "shared_gate_up": draw(HID, 2 * N_SHARED * WIDTH,
                                scale=HID ** -0.5),
         "shared_down": draw(N_SHARED * WIDTH, HID, scale=WIDTH ** -0.5)}
    cfg = {"num_experts_per_tok": TOP_K, "norm_topk_prob": True,
           "num_shared_experts": N_SHARED}
    return p, cfg, draw(n, HID)


def test_sixteen_shares_and_the_shared_mean_once_are_the_uncut_layer():
    """The test that ties the share to the model: each of 16 chips routes
    over all 128 by sigmoid scores without a bias, multiplies the pairs of
    its own 8 experts, and the sixteen parts, with the mean of the four
    shared experts counted once, are the uncut reference layer.
    ``parallel/moe.py`` took this as it stood."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    p, cfg, h = _layer(3)
    with jax.default_matmul_precision("highest"):
        whole, logits, _ = REF.ffn(jnp.asarray(h), p, cfg, (0, E_ROUTER))
        shared = REF.shared_mean(jnp.asarray(h), p["shared_gate_up"],
                                 p["shared_down"], N_SHARED)
    whole = np.asarray(whole)
    s = 1 / (1 + np.exp(-np.asarray(logits, "float64")))
    chosen = np.argsort(-s, axis=-1, kind="stable")[:, :TOP_K]
    held = E_ROUTER // SHARES
    parts, pairs = [], 0
    for rank in range(SHARES):
        first = rank * held
        mine = dict(p, gate_up=p["gate_up"][first:first + held],
                    down=p["down"][first:first + held])
        out, counts, _ = moe_routed_tokens(
            jnp.asarray(h), jnp.asarray(h), mine["router"], mine["gate_up"],
            mine["down"], top_k=TOP_K, activation="silu",
            precision=jax.lax.Precision.HIGHEST, score="sigmoid",
            held_first=first)
        counts = np.asarray(counts)
        assert counts.shape == (E_ROUTER,) and counts.sum() == len(h) * TOP_K
        here = int(((chosen >= first) & (chosen < first + held)).sum())
        assert counts[first:first + held].sum() == here
        pairs += here
        with jax.default_matmul_precision("highest"):
            want, _, _ = REF.ffn(jnp.asarray(h), mine, cfg, (first, held),
                                 shared=False)
        assert np.abs(np.asarray(out) - np.asarray(want)).max() \
            < 1e-5 * np.abs(whole).max()
        parts.append(np.asarray(out))
    assert pairs == len(h) * TOP_K           # every pair lives on one chip
    total = np.sum(parts, axis=0) + np.asarray(shared)
    assert np.abs(total - whole).max() < 1e-5 * np.abs(whole).max()
    # the shared mean counted once, not once a chip; and a part is a part
    assert np.abs(total + np.asarray(shared) - whole).max() \
        > 0.05 * np.abs(whole).max()
    assert np.abs(parts[0] + np.asarray(shared) - whole).max() \
        > 0.1 * np.abs(whole).max()


def test_logit_scale_multiplies_only_where_it_is_not_one():
    cfg = _cfg()
    model = BUILDER.model_args(cfg)
    ids = _prompt(6, 12)
    scope = pt.Scope()
    one = _forward_logits(model, ids, scope)
    half = _forward_logits(dict(model, logit_scale=0.5), ids, scope)
    np.testing.assert_allclose(half, 0.5 * one, rtol=1e-6, atol=1e-7)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        from paddle_tpu.models.llama import build_llama_forward
        build_llama_forward(1, 12, name="llama", **model)
    assert "scale" not in [op.type for op in main.global_block().ops[-3:]]
