"""``solar-open2-250b`` at a small size (PR 43): one chip's share of an
expert-parallel group, a shared expert, and a delta rule whose decay is a
vector a key channel.

* **The share** (``parallel/moe.py`` ``moe_routed_tokens(held_first=)``):
  for 16 shares of a 320-wide router at toy widths, the sixteen partial
  outputs, the shared expert counted once, add up to the uncut reference
  layer; a share's ``counts`` over its held experts are its pairs; absent
  experts' pairs reach no matmul row (a NaN planted in a pad row's input
  reaches nothing).
* **Ops** (``ops/gated_delta_ops.py``): the chunked op with ``G`` [.., H,
  Dk] against the recurrence taken token by token, with strong decay (``g``
  down to -8 a token), padding behind ``valid`` and an initial state; the
  step op against the recurrence; the kernels (interpret mode) against the
  XLA formulations; the counter of ops built with a decay a channel.
* **Model** (``models/llama.py``) against the benchmark's plain reference:
  prefill then eight cached decode steps through the paged
  ``GenerationEngine`` in a slot that was used and left, between live
  neighbours, logits not tokens; ``cache_spec``'s states beside experts;
  spans, counters, and what walks pages only refused.
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
TOL = 2.0 ** -10          # of the logits' range; float32 reads 1e-5 here


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "solar_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "solar-open2-250b")
BUILDER = _load("builders", "solar_open2_engine")


def _cfg(**over):
    """The published keys at a toy size: hidden 64; layer 0 softmax
    attention of 8 query over 2 KV heads of 16 with an output gate, layers
    1-3 KDA of 4 heads of 16 with 4 taps; a router of 16 experts, 3 a
    token, of which experts 4..7 are held, beside a shared expert."""
    cfg = {"model_type": "solar_open2", "hidden_size": 64,
           "num_hidden_layers": 4, "num_attention_heads": 8, "head_dim": 16,
           "num_key_value_heads": 2, "vocab_size": 97,
           "intermediate_size": 0, "moe_intermediate_size": 32,
           "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
           "first_k_dense_replace": 0, "use_rope": False, "gqa_layers": [0],
           "use_gqa_gate": True, "kda_use_full_proj": False,
           "kda_allow_neg_eigval": True, "n_routed_experts": 4,
           "n_shared_experts": 1, "norm_topk_prob": True,
           "routed_scaling_factor": 1, "num_experts_per_tok": 3,
           "linear_attn_config": {"short_conv_kernel_size": 4,
                                  "head_dim": 16, "num_heads": 4,
                                  "num_kv_heads": None},
           "expert_share": {"router_experts": 16, "first": 4},
           "as_run": {"attention_precision": "highest"},
           "assumed": {"low_rank": 8, "expert_bias_scale": 0.02,
                       "eos_id": -1},
           "check_tolerance": {
               "near_tie_margin_share_of_router_range": 4e-4}}
    cfg.update(over)
    return cfg


def _engine(cfg=None, seed=11, **kw):
    from paddle_tpu.serving import GenerationEngine

    cfg = cfg or _cfg()
    args = dict(num_slots=3, max_seq_len=256,
                prefill_buckets=[8, 32, 192], page_tokens=PAGE,
                attn_impl="xla", keep_logits=True, prefill_chunk=0,
                prefix_reuse=False, speculate=False, eos_id=-1,
                deadline_ms=600000)
    args.update(kw)
    eng = GenerationEngine(BUILDER.model_args(cfg), **args)
    if "scope" not in kw:
        BUILDER.seed_delta_gates(eng.scope, cfg, seed)
        BUILDER.seed_expert_bias(eng.scope, cfg, seed)
    return eng


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 97, n).tolist()


def _off_reference(eng, cfg, prompt, res):
    """How far a result's logits lie off the reference's full forward
    over prompt plus generated tokens, as a share of its range."""
    n, new = len(prompt), len(res["tokens"])
    params = REF.params_from_scope(eng.scope, cfg, "llama")
    seq = np.asarray(prompt + res["tokens"], "int32")
    want = np.asarray(REF.forward(params, seq, cfg,
                                  np.arange(n - 1, n - 1 + new)))
    got = np.stack(res["logits"])
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


# ---------------------------------------------------------------------------
# the share
# ---------------------------------------------------------------------------

E_ROUTER, SHARES, TOP_K, HID, WIDTH = 320, 16, 8, 32, 16


def _layer(seed, n=48):
    """One uncut expert layer at toy widths behind a 320-wide router, and
    ``n`` rows."""
    rng = np.random.default_rng(seed)

    def draw(*shape, scale=1.0):
        return (scale * rng.normal(size=shape)).astype("float32")

    p = {"router": draw(HID, E_ROUTER, scale=HID ** -0.5),
         "bias": draw(E_ROUTER, scale=0.02),
         "gate_up": draw(E_ROUTER, HID, 2 * WIDTH, scale=HID ** -0.5),
         "down": draw(E_ROUTER, WIDTH, HID, scale=WIDTH ** -0.5),
         "shared_gate_up": draw(HID, 2 * WIDTH, scale=HID ** -0.5),
         "shared_down": draw(WIDTH, HID, scale=WIDTH ** -0.5)}
    cfg = {"num_experts_per_tok": TOP_K, "norm_topk_prob": True,
           "routed_scaling_factor": 1, "n_shared_experts": 1}
    return p, cfg, draw(n, HID)


def _share(p, first, count):
    return dict(p, gate_up=p["gate_up"][first:first + count],
                down=p["down"][first:first + count])


def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """The test that ties the share to the model: each of 16 chips routes
    over all 320, multiplies the pairs of its own 20 experts, and the
    sixteen parts, with the shared expert counted once, are the uncut
    reference layer.  A share's ``counts`` over its experts are its pairs
    and the program's part is the reference's for the same range."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import moe_routed_tokens

    p, cfg, h = _layer(3)
    with jax.default_matmul_precision("highest"):
        whole, logits, _ = REF.ffn(jnp.asarray(h), p, cfg, (0, E_ROUTER))
        shared = REF._swiglu(jnp.asarray(h), p["shared_gate_up"],
                             p["shared_down"])
    whole = np.asarray(whole)
    s = 1 / (1 + np.exp(-np.asarray(logits, "float64")))
    chosen = np.argsort(-(s + p["bias"]), axis=-1, kind="stable")[:, :TOP_K]
    held = E_ROUTER // SHARES
    parts, pairs = [], 0
    for rank in range(SHARES):
        first = rank * held
        mine = _share(p, first, held)
        out, counts, _ = moe_routed_tokens(
            jnp.asarray(h), jnp.asarray(h), mine["router"], mine["gate_up"],
            mine["down"], top_k=TOP_K, activation="silu",
            precision=jax.lax.Precision.HIGHEST, score="sigmoid",
            expert_bias=mine["bias"], held_first=first)
        counts = np.asarray(counts)
        # the router's whole width is counted; the share's slice is the
        # pairs whose expert it holds
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
    # and the parts are parts: no share is the whole
    assert np.abs(parts[0] + np.asarray(shared) - whole).max() \
        > 0.1 * np.abs(whole).max()


@pytest.mark.parametrize("n", [3, 40, 200])
def test_pairs_of_absent_experts_and_pad_rows_reach_no_matmul(n):
    """Rows behind ``valid`` route nowhere here (a NaN planted there
    reaches neither the valid rows' outputs nor the counts), whether the
    held pairs fill less than one run of sorted pairs or several."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import RUN_ROWS, moe_routed_tokens

    p, cfg, h = _layer(5, n=n + 9)
    first, held = 40, 120                 # many held: several runs at 200
    mine = _share(p, first, held)
    valid = np.arange(n + 9) < n
    poisoned = h.copy()
    poisoned[n:] = np.nan

    def run(x):
        return moe_routed_tokens(
            jnp.asarray(x), jnp.asarray(x), mine["router"], mine["gate_up"],
            mine["down"], top_k=TOP_K, activation="silu",
            valid=jnp.asarray(valid), precision=jax.lax.Precision.HIGHEST,
            score="sigmoid", expert_bias=mine["bias"], held_first=first)

    out, counts, _ = run(poisoned)
    clean, counts2, _ = run(np.where(valid[:, None], h, 0.0))
    assert np.isfinite(np.asarray(out)[:n]).all()
    np.testing.assert_array_equal(np.asarray(out)[:n], np.asarray(clean)[:n])
    assert int(np.asarray(counts).sum()) == n * TOP_K
    with jax.default_matmul_precision("highest"):
        want, _, _ = REF.ffn(jnp.asarray(h[:n]), mine, cfg, (first, held),
                             shared=False)
    assert np.abs(np.asarray(out)[:n] - np.asarray(want)).max() < 1e-5
    if n == 200:
        assert int(np.asarray(counts)[first:first + held].sum()) > RUN_ROWS


@pytest.mark.parametrize("run", [64, 192, 256])
@pytest.mark.parametrize("pairs", ["none", "one", "a_run_less_one", "a_run",
                                   "three_runs_and_a_tail"])
def test_held_share_is_the_loop_over_the_held_experts(pairs, run):
    """``_held_share`` on exactly so many held pairs, in runs of ``run``
    sorted pairs, against a float64 loop over the held experts: nothing
    for none, one trip up to a whole run, several trips and a tail, with
    the rows behind the prompt's end (NaN) in no pair.  A run of 64 is
    added to ``out`` at once, one of 256 in two pieces of ``SCATTER_ROWS``,
    one of 192 in two of which the second starts early."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.moe import _held_share

    count = {"none": 0, "one": 1, "a_run_less_one": run - 1, "a_run": run,
             "three_runs_and_a_tail": 3 * run + 5}[pairs]
    rng = np.random.default_rng(count + run)
    n, real, held, top_k = 150, 141, 6, TOP_K
    assert count <= real * top_k
    x = rng.normal(size=(n, HID)).astype("float32")
    x[real:] = np.nan
    w = rng.uniform(size=(n, top_k)).astype("float32")
    gate_up = rng.normal(size=(held, HID, 2 * WIDTH)).astype("float32") \
        * HID ** -0.5
    down = rng.normal(size=(held, WIDTH, HID)).astype("float32") \
        * WIDTH ** -0.5
    local = np.full(n * top_k, held, "int32")
    at = rng.choice(real * top_k, count, replace=False)
    local[at] = rng.integers(0, held, count)
    local = local.reshape(n, top_k)
    out = np.asarray(jax.jit(lambda *a: _held_share(
        *a, "silu", jax.lax.Precision.HIGHEST, run=run))(
            jnp.asarray(x), jnp.asarray(local), jnp.asarray(w),
            jnp.asarray(gate_up), jnp.asarray(down)))
    want = np.zeros((n, HID))
    for t, slot in zip(*np.nonzero(local < held)):
        e = local[t, slot]
        h = x[t].astype("float64") @ gate_up[e]
        a = h[:WIDTH] / (1 + np.exp(-h[:WIDTH])) * h[WIDTH:]
        want[t] += w[t, slot] * (a @ down[e])
    assert out.shape == (n, HID) and np.isfinite(out).all()
    assert np.abs(out - want).max() < 1e-5 * max(1.0, np.abs(want).max())
    # a row with no held pair, and every row behind the end, adds nothing
    assert not out[(local == held).all(-1)].any()


def test_a_share_outside_the_routers_experts_is_refused():
    x = layers.data("x", [1, 4, 8], append_batch_size=False)
    with pytest.raises(ValueError, match="holds experts"):
        layers.moe_routed_ffn(x, x, 16, 2, 8, held=(12, 8))


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def _operands(seed, B, T, H=3, Dk=16, Dv=12, decay=2.0, floor=None):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(B, T, H, Dk))) * Dk ** -0.5
    k = unit(rng.normal(size=(B, T, H, Dk)))
    v = rng.normal(size=(B, T, H, Dv))
    g = -decay * np.abs(rng.normal(size=(B, T, H, Dk)))
    if floor is not None:
        g = np.maximum(g, floor)
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(B, T, H))))
    return [x.astype("float32") for x in (q, k, v, g, beta)]


def _recurrence(q, k, v, g, beta, s0=None, valid=None):
    """Token by token, in float64: ``S = (I - b k k^T) Diag(exp(g)) S + b
    k v^T``."""
    q, k, v, g, beta = (np.asarray(x, "float64") for x in (q, k, v, g, beta))
    B, T, H, Dk = q.shape
    s = np.zeros((B, H, Dk, v.shape[-1])) if s0 is None \
        else np.asarray(s0, "float64").copy()
    out = np.zeros(v.shape)
    for b in range(B):
        for t in range(T if valid is None else int(valid[b])):
            sd = np.exp(g[b, t])[:, :, None] * s[b]
            r = v[b, t] - np.einsum("hkv,hk->hv", sd, k[b, t])
            s[b] = sd + k[b, t][:, :, None] \
                * (beta[b, t][:, None] * r)[:, None, :]
            out[b, t] = np.einsum("hkv,hk->hv", s[b], q[b, t])
    return out, s


def _run(build, feed, scope=None):
    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        fetches = build()
    exe = pt.Executor()
    scope = scope or pt.Scope()
    exe.run(startup, scope=scope)
    return exe.run(main, feed=feed, fetch_list=fetches, scope=scope)


def _chunk_program(B, T, H, Dk, Dv, state0=False, valid=False):
    def data(name, shape, dtype="float32"):
        return layers.data(name, shape, dtype=dtype, append_batch_size=False)

    q, k = data("q", [B, T, H, Dk]), data("k", [B, T, H, Dk])
    v = data("v", [B, T, H, Dv])
    g, beta = data("g", [B, T, H, Dk]), data("beta", [B, T, H])
    kw = {}
    if state0:
        kw["state0"] = data("s0", [B, H, Dk, Dv])
    if valid:
        kw["valid"] = data("valid", [B], "int32")
    return list(layers.gated_delta_chunk(q, k, v, g, beta, **kw))


@pytest.mark.parametrize("T,decay,state0", [
    (64, 0.3, False), (128, 2.0, False), (150, 2.0, True), (5, 0.3, True),
])
def test_chunked_op_with_a_decay_a_channel_is_the_recurrence(T, decay,
                                                              state0):
    B, H, Dk, Dv = 2, 3, 16, 12
    ops = _operands(T, B, T, H, Dk, Dv, decay)
    feed = dict(zip("q k v g beta".split(), ops))
    s0 = None
    if state0:
        s0 = np.random.default_rng(9).normal(size=(B, H, Dk, Dv)) \
            .astype("float32")
        feed["s0"] = s0
    chan0 = stat_get("gated_delta_lowered_channel_decay")
    out, state = _run(lambda: _chunk_program(B, T, H, Dk, Dv, state0), feed)
    want, want_s = _recurrence(*ops, s0=s0)
    assert np.abs(out - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    assert np.abs(state - want_s).max() < 2e-5 * max(1.0,
                                                     np.abs(want_s).max())
    assert stat_get("gated_delta_lowered_channel_decay") == chan0 + 1


def test_strong_decay_a_channel_neither_overflows_nor_drifts():
    """``g`` down to -8 a token a channel, others near 0 in the same head:
    over a chunk's 64 tokens the sums of log decay reach -500, which no
    factored form ``exp(cum_t) exp(-cum_i)`` survives in float32.  Every
    exponent the op takes is non-positive."""
    B, T, H, Dk, Dv = 1, 192, 2, 16, 8
    q, k, v, g, beta = _operands(4, B, T, H, Dk, Dv, decay=6.0, floor=-8.0)
    g[..., ::2] *= 1e-3                      # slow channels beside fast
    assert g.min() == -8.0 and g.reshape(-1, 64, H, Dk).sum(1).min() < -200
    out, state = _run(lambda: _chunk_program(B, T, H, Dk, Dv),
                      dict(zip("q k v g beta".split(), (q, k, v, g, beta))))
    want, want_s = _recurrence(q, k, v, g, beta)
    assert np.isfinite(out).all() and np.isfinite(state).all()
    assert np.abs(out - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    assert np.abs(state - want_s).max() < 2e-5 * np.abs(want_s).max()


@pytest.mark.parametrize("n", [1, 17, 64, 70])
def test_rows_behind_valid_reach_nothing_with_a_decay_a_channel(n):
    B, T, H, Dk, Dv = 1, 128, 2, 16, 8
    ops = _operands(n, B, T, H, Dk, Dv)
    for x in ops:
        x[:, n:] = np.nan                    # the pad tail holds anything
    feed = dict(zip("q k v g beta".split(), ops), valid=np.array([n], "int32"))
    out, state = _run(
        lambda: _chunk_program(B, T, H, Dk, Dv, valid=True), feed)
    want, want_s = _recurrence(*(x[:, :n] for x in ops))
    assert np.isfinite(out[:, :n]).all() and np.isfinite(state).all()
    assert np.abs(out[:, :n] - want).max() < 2e-5
    assert np.abs(state - want_s).max() < 2e-5


def test_step_op_with_a_decay_a_channel_moves_live_rows_only():
    """One row a slot over the state variable: live rows move on as the
    recurrence does, in place; a dead row's state and the trash row stay
    as they were."""
    n, H, Dk, Dv = 4, 3, 16, 12
    q, k, v, g, beta = (x[0][:, None] for x in _operands(7, 1, n, H, Dk, Dv))
    state0 = np.random.default_rng(2).normal(size=(n + 1, H, Dk, Dv)) \
        .astype("float32")
    live = np.array([1, 0, 1, 1], "int32")

    def build():
        def data(name, shape, dtype="float32"):
            return layers.data(name, shape, dtype=dtype,
                               append_batch_size=False)

        block = pt.default_main_program().global_block()
        state = block.create_var(name="st", persistable=True,
                                 shape=[n + 1, H, Dk, Dv], dtype="float32",
                                 stop_gradient=True)
        out = layers.gated_delta_step(
            data("q", [n, 1, H, Dk]), data("k", [n, 1, H, Dk]),
            data("v", [n, 1, H, Dv]), data("g", [n, 1, H, Dk]),
            data("beta", [n, 1, H]), state, data("live", [n], "int32"))
        return [out, state]

    scope = pt.Scope()
    scope.set_var("st", state0.copy())
    chan0 = stat_get("gated_delta_lowered_channel_decay")
    out, state = _run(build, dict(q=q, k=k, v=v, g=g, beta=beta, live=live),
                      scope=scope)
    assert stat_get("gated_delta_lowered_channel_decay") == chan0 + 1
    for i in range(n):
        want, want_s = _recurrence(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                   g[i:i + 1], beta[i:i + 1],
                                   s0=state0[i:i + 1])
        if live[i]:
            assert np.abs(out[i] - want[0]).max() < 1e-5
            assert np.abs(state[i] - want_s[0]).max() < 1e-5
        else:
            np.testing.assert_array_equal(state[i], state0[i])
    np.testing.assert_array_equal(state[n], state0[n])


def _fused(*ops, **kw):
    """The fused scan kernel in interpret mode, on numpy operands."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import gated_delta as P

    out, state = P.chunk(*(jnp.asarray(x) for x in ops), interpret=True,
                         **{n: jnp.asarray(x) for n, x in kw.items()})
    return np.asarray(out), np.asarray(state)


@pytest.mark.parametrize("T,Dk,Dv", [(192, 16, 16), (128, 128, 128)])
def test_chunk_kernel_scales_the_states_rows(T, Dk, Dv):
    """The scan as the Pallas kernel (interpret mode): the chunk's decay
    is a row of a sublane tile, turned into the column that scales the
    state's rows."""
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as G
    from paddle_tpu.ops.pallas import gated_delta as P

    ops = _operands(T, 1, T, 2, Dk, Dv)
    assert P.chunk_supported(ops[0].shape, G.CHUNK)
    want, want_s = G.chunked(*(jnp.asarray(x) for x in ops))
    out, state = _fused(*ops)
    assert np.abs(out - np.asarray(want)).max() < 1e-5
    assert np.abs(state - np.asarray(want_s)).max() < 1e-5


@pytest.mark.parametrize("T,Dk,Dv", [(192, 8, 16), (128, 96, 192),
                                     (256, 128, 128)])
def test_chunk_kernel_with_a_decay_a_channel_is_the_scan(T, Dk, Dv):
    """From a state, a prompt that ends inside a chunk: against the XLA
    form and against the recurrence."""
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as G

    ops = _operands(T, 2, T, 2, Dk, Dv)
    s0 = np.random.default_rng(2).normal(size=(2, 2, Dk, Dv)) \
        .astype("float32")
    valid = np.asarray([T, T - 70], "int32")
    want, want_s = G.chunked(*(jnp.asarray(x) for x in ops),
                             s0=jnp.asarray(s0), valid=jnp.asarray(valid))
    out, state = _fused(*ops, s0=s0, valid=valid)
    assert np.abs(out - np.asarray(want)).max() < 1e-5
    assert np.abs(state - np.asarray(want_s)).max() < 1e-5
    true, true_s = _recurrence(*ops, s0=s0, valid=valid)
    assert np.abs(out - true).max() < 2e-5
    assert np.abs(state - true_s).max() < 2e-5


@pytest.mark.parametrize("n", [1, 63, 64, 70, 129])
def test_chunk_kernel_with_a_decay_a_channel_reads_nothing_behind_valid(n):
    B, T, H, Dk, Dv = 1, 192, 2, 16, 8
    ops = _operands(n, B, T, H, Dk, Dv)
    padded = [x.copy() for x in ops]
    for x in padded:
        x[:, n:] = np.nan
    s0 = np.random.default_rng(n).normal(size=(B, H, Dk, Dv)) \
        .astype("float32")
    out, state = _fused(*padded, s0=s0, valid=np.array([n], "int32"))
    want, want_s = _recurrence(*(x[:, :n] for x in ops), s0=s0)
    assert np.isfinite(out).all() and np.isfinite(state).all()
    assert np.abs(out[:, :n] - want).max() < 2e-5
    assert np.abs(state - want_s).max() < 2e-5
    assert not out[:, n:].any()


def test_chunk_kernel_with_a_decay_a_channel_carries_the_state():
    T, cut = 200, 77
    ops = _operands(8, 1, T, 2, 16, 8)
    whole, whole_s = _fused(*ops)
    o1, s1 = _fused(*[x[:, :cut] for x in ops])
    o2, s2 = _fused(*[x[:, cut:] for x in ops], s0=s1)
    assert np.abs(np.concatenate([o1, o2], 1) - whole).max() < 2e-5
    assert np.abs(s2 - whole_s).max() < 2e-5


def test_chunk_kernel_under_strong_decay_a_channel():
    """``test_strong_decay_a_channel_neither_overflows_nor_drifts``
    through the kernel: every exponent it takes is non-positive."""
    B, T, H, Dk, Dv = 1, 192, 2, 16, 8
    q, k, v, g, beta = _operands(4, B, T, H, Dk, Dv, decay=6.0, floor=-8.0)
    g[..., ::2] *= 1e-3
    out, state = _fused(q, k, v, g, beta)
    want, want_s = _recurrence(q, k, v, g, beta)
    assert np.isfinite(out).all() and np.isfinite(state).all()
    assert np.abs(out - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    assert np.abs(state - want_s).max() < 2e-5 * np.abs(want_s).max()


@pytest.mark.parametrize("H,Dk,Dv", [(3, 16, 16), (16, 128, 128)])
def test_step_kernel_takes_its_decay_from_the_tile(H, Dk, Dv):
    """The step kernel (interpret mode) with a decay a channel against
    the three contractions; dead slots and the trash row bit for bit
    untouched."""
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as G
    from paddle_tpu.ops.pallas import gated_delta as P

    n = 3
    q, k, v, g, beta = (jnp.asarray(x[0]) for x in
                        _operands(H, 1, n, H, Dk, Dv))
    state = jnp.asarray(np.random.default_rng(1).normal(
        size=(n + 1, H, Dk, Dv)).astype("float32"))
    live = np.array([1, 0, 1])
    assert P.step_supported(state.shape)
    out, new = P.step(q, k, v, g, beta, state, jnp.asarray(live, jnp.int32),
                      interpret=True)
    want, want_s = G.step(q, k, v, g, beta, state, jnp.asarray(live, bool))
    m = live.astype(bool)
    assert np.abs(np.asarray(out)[m] - np.asarray(want)[m]).max() < 1e-5
    assert np.abs(np.asarray(new) - np.asarray(want_s)).max() < 1e-5
    np.testing.assert_array_equal(np.asarray(new)[1], np.asarray(state)[1])
    np.testing.assert_array_equal(np.asarray(new)[n], np.asarray(state)[n])


def test_a_chunk_that_is_not_whole_blocks_is_refused():
    import jax.numpy as jnp

    from paddle_tpu.ops import gated_delta_ops as G

    ops = [jnp.asarray(x) for x in _operands(1, 1, 40, 2, 16, 8)]
    with pytest.raises(ValueError, match="whole blocks"):
        G.chunked(*ops, chunk=40)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def test_cache_spec_keeps_both_states_beside_experts():
    from paddle_tpu.models.llama import cache_spec

    model = BUILDER.model_args(_cfg())
    spec = cache_spec("llama", 4, model["layer_pattern"], num_slots=5,
                      num_pages=9, page_tokens=PAGE, num_kv_heads=2,
                      head_dim=16, hidden=64)
    by_layer = {i: [e for e in spec if e["layer"] == i] for i in range(4)}
    assert [e["kind"] for e in by_layer[0]] == ["pages", "pages"]
    for i in (1, 2, 3):
        assert [(e["name"], e["shape"]) for e in by_layer[i]] == [
            (f"llama.conv_state_{i}", [6, 3, 3 * 4 * 16]),
            (f"llama.delta_state_{i}", [6, 4, 16, 16])]
    held = model["layer_pattern"][1]["ffn"]["held"]
    assert held == (4, 4) and model["layer_pattern"][1]["ffn"]["experts"] == 16


def test_prefill_then_cached_decode_in_a_reused_slot_between_neighbours():
    """Slots 0 and 1 decode all the while; slot 2 serves a request, is
    left, and takes the compared ones: the paged prefill and eight cached
    decode steps are the reference's full forward (logits, not tokens),
    and so is a prompt of more than two chunks.  The engine books what
    the share did."""
    cfg = _cfg()
    eng = _engine(cfg)
    try:
        sides = [eng.submit(_prompt(50 + i, 9 + i), 60) for i in range(2)]
        first = eng.submit(_prompt(52, 30), 6)
        assert first.result(300)["slot"] == 2
        res = {}
        for n in (5, 150):
            prompt = _prompt(60 + n, n)
            r = eng.generate(prompt, 9, timeout=300)
            assert r["slot"] == 2
            res[n] = (prompt, r)
        rest = [f.result(300) for f in sides]
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    assert [r["slot"] for r in rest] == [0, 1]
    for prompt, r in res.values():
        assert len(r["logits"]) == 9
        assert np.stack(r["router_logits"]).shape == (9, 4, 16)
        assert _off_reference(eng, cfg, prompt, r) < TOL
    for f, r in zip((50, 51), rest):
        assert _off_reference(eng, cfg, _prompt(f, 9 + f - 50), r) < TOL
    assert counters["slot_state_writes"] == 5
    assert counters["delta_state_steps"] % 3 == 0
    # dropless over the router's 16; a quarter of them held, more or less
    assert counters["moe_tokens_dropped"] == 0
    assert counters["moe_pairs_routed"] == counters["moe_tokens_routed"]
    assert 0.1 < counters["moe_pairs_held"] / counters["moe_pairs_routed"] \
        < 0.45
    assert counters["moe_shared_expert_rows"] * 3 \
        == counters["moe_pairs_routed"]


def test_spans_say_what_the_share_and_the_scan_did():
    from paddle_tpu import telemetry

    eng = _engine()
    routed0 = stat_get("moe_pairs_routed")
    try:
        eng.generate(_prompt(41, 70), 4, timeout=300)
        spans = [s for s in telemetry.get_spans() if s.end is not None]
    finally:
        eng.close()
    prefill = [s for s in spans if s.name == "generation/prefill"][-1]
    assert (prefill.attrs["scan_tokens"], prefill.attrs["scan_chunks"],
            prefill.attrs["scan_pad_chunks"]) == (70, 3, 1)
    fetch = [s for s in spans if s.name == "generation/prefill_fetch"][-1]
    assert fetch.attrs["pairs_routed"] == 4 * 70 * 3
    assert 0 < fetch.attrs["pairs_held"] < fetch.attrs["pairs_routed"]
    steps = [s for s in spans if s.name == "generation/decode_step"
             and "pairs_held" in s.attrs]
    assert steps
    for s in steps[-3:]:
        assert s.attrs["state_slots"] == 1 and s.attrs["pairs_routed"] == 12
        assert 0 <= s.attrs["pairs_held"] <= 12
        assert 0 <= s.attrs["experts_held_touched"] <= 3
        assert s.attrs["experts_held_touched"] <= s.attrs["experts_touched"]
    assert stat_get("moe_pairs_routed") >= routed0 + 4 * 70 * 3


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"prefill_chunk": 8}, "prefill_chunk > 0"),
    ({"speculate": True}, "speculate"),
    ({"role": "prefill"}, "KV-segment handoff"),
    ({"role": "decode"}, "KV-segment handoff"),
])
def test_what_walks_pages_only_is_refused_beside_experts_too(kw, reason):
    with pytest.raises(ValueError, match="slot state") as e:
        _engine(**kw)
    assert reason in str(e.value)


def test_the_uncut_layer_runs_through_the_same_program():
    """``held`` covering every expert of the router is the uncut model:
    the reference given all 16 agrees."""
    cfg = _cfg(n_routed_experts=16,
               expert_share={"router_experts": 16, "first": 0})
    eng = _engine(cfg)
    try:
        prompt = _prompt(77, 40)
        res = eng.generate(prompt, 5, timeout=300)
        counters = eng.stats()["counters"]
    finally:
        eng.close()
    assert _off_reference(eng, cfg, prompt, res) < TOL
    assert counters["moe_pairs_held"] == counters["moe_pairs_routed"]
