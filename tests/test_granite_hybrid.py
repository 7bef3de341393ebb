"""State-space duality layers (PR 59): a matrix of state a slot a layer
that every token decays by a number a head and writes an outer product
into (Mamba-2, ``granite-4.0-h-micro``).

* **Ops** (``ops/ssd_ops.py``): the chunked op against the recurrence
  taken token by token in float64 (``valid`` inside a chunk, at a chunk's
  edge, behind a whole chunk; an initial state; two calls that carry the
  state against one); rows behind ``valid`` (a NaN planted there) reach
  neither outputs nor state; the step is the recurrence's one token, moves
  ``live`` rows only, in place, and the trash row takes a warm-up's write.
* **Kernels** (``ops/pallas/ssd.py``, interpret mode) against the XLA
  formulations, at the same places.
* **Model** (``models/llama.py``): ``mixer: ssd`` with the family's four
  multipliers, no rotary embedding and the tied head, against the
  benchmark's plain reference, uncached and through the paged
  ``GenerationEngine`` (a reused slot between live neighbours),
  ``cache_spec``'s two states a layer, spans and counters, and planted
  faults that must NOT pass.  (The kernels' compile for a described v5e is
  with the other such compiles, ``tests/test_paged_decode_attention.py``.)
"""
import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.monitor import stat_get

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
# of the logits' range.  Nothing here rounds below float32: the program
# and the reference differ by the order of float32 sums (the chunked
# rearrangement against the token-by-token recurrence) and read 1e-7 to
# 2e-6 at these sizes; a planted fault reads 1e-2 to 1
TOL = 2.0 ** -12


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "granite_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference", "granite-4.0-h-micro")
BUILDER = _load("builders", "granite_hybrid_engine")
PERIOD = ["mamba"] * 2 + ["attention"] + ["mamba"]


def _cfg(**over):
    """The published keys at a toy size: hidden 64, three state-space
    layers of 8 heads of 16 over 16 state rows (4 taps, a bias) and one of
    4 query over 2 KV heads of 16 without position embedding."""
    cfg = {"vocab_size": 97, "hidden_size": 64, "intermediate_size": 96,
           "shared_intermediate_size": 96, "num_hidden_layers": 4,
           "layer_types": list(PERIOD), "num_attention_heads": 4,
           "num_key_value_heads": 2, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": True, "mamba_n_heads": 8,
           "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
           "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_expand": 2,
           "mamba_proj_bias": False, "attention_bias": False,
           "num_local_experts": 0, "hidden_act": "silu",
           "position_embedding_type": "nope",
           "normalization_function": "rmsnorm",
           "embedding_multiplier": 12, "attention_multiplier": 0.0625,
           "residual_multiplier": 0.22, "logits_scaling": 8,
           "as_run": {"attention_precision": "highest"},
           "assumed": {"eos_id": -1}}
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
        # (the convolution's bias starts at zero: give it values)
        for i, kind in enumerate(cfg["layer_types"]):
            if kind == "mamba":
                eng.scope.set_var(
                    f"llama.blk{i}.ssd_conv.b",
                    np.random.default_rng(seed + i).normal(
                        0, 0.3, 8 * 16 + 32).astype("float32"))
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
# ops
# ---------------------------------------------------------------------------

H, P, N = 4, 32, 16          # two heads a lane tile: the kernels' case


def _operands(seed, B, T):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(B, T, H, P))
    dt = 5.0 * np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (B, T, H)))
    a = -rng.uniform(1.0, 16.0, H)
    bm, cm = rng.normal(size=(B, T, N)), rng.normal(size=(B, T, N))
    d = rng.normal(size=H)
    return [t.astype("float32") for t in (x, dt, a, bm, cm, d)]


def _recurrence(x, dt, a, bm, cm, d, s0=None, valid=None):
    """Token by token, in float64; the state ``[B, N, H P]``."""
    x, dt, a, bm, cm, d = (np.asarray(t, "float64")
                           for t in (x, dt, a, bm, cm, d))
    B, T = x.shape[:2]
    s = np.zeros((B, N, H, P)) if s0 is None \
        else np.asarray(s0, "float64").reshape(B, N, H, P).copy()
    out = np.zeros(x.shape)
    for b in range(B):
        for t in range(T if valid is None else int(valid[b])):
            s[b] = np.exp(dt[b, t] * a)[None, :, None] * s[b] \
                + bm[b, t][:, None, None] * (dt[b, t][:, None] * x[b, t])
            out[b, t] = np.einsum("n,nhp->hp", cm[b, t], s[b]) \
                + d[:, None] * x[b, t]
    return out, s.reshape(B, N, H * P)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, "float64"), np.asarray(want, "float64")
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


def _forms(chunk):
    """The chunk op's forms at ``chunk`` tokens a chunk: XLA, and the
    Pallas kernel in interpret mode."""
    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.ops.pallas import ssd

    return {"xla": lambda *t, **kw: ssd_ops.chunked(*t, chunk=chunk, **kw),
            "kernel": lambda *t, **kw: ssd.chunk(
                *t, chunk=chunk, lanes_block=128, interpret=True, **kw)}


# chunks of 16 over 40 rows: ``valid`` inside a chunk, at a chunk's edge,
# behind a whole chunk (the last one all pad), and no pad at all
@pytest.mark.parametrize("valid", [[21, 40], [32, 16], [7, 24], None])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_chunked_scan_is_the_recurrence(form, valid):
    import jax

    ops = _operands(3, 2, 40)
    rng = np.random.default_rng(4)
    s0 = rng.normal(size=(2, N, H * P)).astype("float32")
    if valid is not None:
        # whatever lies behind ``valid`` reaches nothing
        for b, n in enumerate(valid):
            for t in (ops[0], ops[1], ops[3], ops[4]):
                t[b, n:] = np.nan
    want_y, want_s = _recurrence(*[np.nan_to_num(t) for t in ops], s0=s0,
                                 valid=valid)
    v = None if valid is None else np.asarray(valid, "int32")
    with jax.default_matmul_precision("highest"):
        y, s = _forms(16)[form](*ops, s0=s0, valid=v)
    assert np.isfinite(np.asarray(y)).all()
    _close(s, want_s)
    for b in range(2):
        n = 40 if valid is None else valid[b]
        _close(np.asarray(y)[b, :n], want_y[b, :n])
        assert not np.asarray(y)[b, n:].any()      # pad rows read zero


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_two_scans_that_carry_the_state_are_one(form):
    import jax

    ops = _operands(5, 1, 48)
    run = _forms(16)[form]
    with jax.default_matmul_precision("highest"):
        y, s = run(*ops)
        cut = [t[:, :20] if t.ndim > 1 else t for t in ops]
        rest = [t[:, 20:] if t.ndim > 1 else t for t in ops]
        y0, s_mid = run(*cut)
        y1, s1 = run(*rest, s0=s_mid)
    _close(np.concatenate([y0, y1], axis=1), y)
    _close(s1, s)


def test_recurrence_of_the_ops_module_is_the_float64_one():
    from paddle_tpu.ops import ssd_ops

    ops = _operands(6, 2, 19)
    valid = np.asarray([19, 11], "int32")
    y, s = ssd_ops.recurrence(*ops, valid=valid)
    want_y, want_s = _recurrence(*ops, valid=valid)
    _close(s, want_s)
    _close(np.asarray(y)[1, :11], want_y[1, :11])
    _close(np.asarray(y)[0], want_y[0])


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_step_is_one_token_and_moves_live_rows_only(form):
    """Slots 0, 2 and 3 are live, slot 1 is dead: its state stays bit for
    bit, and so does the trash row (row 5) that a warm-up's prefill
    writes; a live slot's step is the recurrence's next token."""
    import jax.numpy as jnp

    from paddle_tpu.ops import ssd_ops
    from paddle_tpu.ops.pallas import ssd

    n = 5
    x, dt, a, bm, cm, d = _operands(7, n, 1)
    state = np.random.default_rng(8).normal(
        size=(n + 1, N, H * P)).astype("float32")
    live = np.asarray([1, 0, 1, 1, 0], "int32")
    row = (x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d)
    if form == "xla":
        y, new = ssd_ops.step(*row, jnp.asarray(state), live.astype(bool))
    else:
        y, new = ssd.step(*row, jnp.asarray(state), jnp.asarray(live),
                          interpret=True, lanes_block=128)
    want_y, want_s = _recurrence(x, dt, a, bm, cm, d, s0=state[:n])
    new = np.asarray(new)
    for i in range(n):
        if live[i]:
            _close(new[i], want_s[i])
            _close(np.asarray(y)[i], want_y[i, 0])
        else:
            assert np.array_equal(new[i], state[i])
    assert np.array_equal(new[n], state[n])


def test_the_route_is_counted_and_a_downgrade_logged_once(monkeypatch,
                                                          caplog):
    import jax

    from paddle_tpu.ops import ssd_ops

    class Ctx:
        mesh = None

    assert ssd_ops._kernel_route(Ctx, "ssd_step") == (False, None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd_ops._kernel_route(Ctx, "ssd_step") == (True, None)
    ref0 = stat_get("ssd_lowered_reference")
    ssd_ops._downgrades_logged.clear()
    with caplog.at_level("WARNING"):
        for _ in range(2):
            ssd_ops._lowered("reference", "ssd_step over a toy state")
    assert stat_get("ssd_lowered_reference") == ref0 + 2
    assert sum("not the Pallas kernel" in r.message
               for r in caplog.records) == 1


def test_kernels_say_what_they_take():
    from paddle_tpu.ops.pallas import ssd

    assert ssd.chunk_supported((1, 1024, 64, 64), 128, 128)
    assert ssd.step_supported((129, 128, 4096))
    # heads that do not divide a lane tile, channels that are no whole
    # tile: the XLA form runs
    assert not ssd.chunk_supported((1, 64, 4, 48), 16, 16)
    assert not ssd.chunk_supported((1, 64, 3, 32), 16, 16)
    assert not ssd.step_supported((3, 16, 96))
    assert ssd._lane_block(1024, 4096) == 1024
    assert ssd._lane_block(1024, 384) == 384
    assert ssd._lane_block(64, 256) == 128


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = _engine()
    yield eng
    eng.close()


def test_every_state_space_layer_has_two_states(engine):
    from paddle_tpu.models.llama import cache_spec

    spec = cache_spec("llama", 4, engine.model["layer_pattern"],
                      num_slots=3, num_pages=engine.num_pages,
                      page_tokens=PAGE, num_kv_heads=2, head_dim=16,
                      hidden=64)
    assert [(e["layer"], e["kind"]) for e in spec] == [
        (0, "slot_state"), (0, "slot_state"), (1, "slot_state"),
        (1, "slot_state"), (2, "pages"), (2, "pages"), (3, "slot_state"),
        (3, "slot_state")]
    # slots + the trash row; 3 rows of x | B | C (8 x 16 + 2 x 16); the
    # state's 16 rows over all 128 channels
    assert spec[0]["name"] == "llama.conv_state_0"
    assert spec[0]["shape"] == [4, 3, 160]
    assert spec[1]["name"] == "llama.ssm_state_0"
    assert spec[1]["shape"] == [4, 16, 128]
    assert engine.cache_names == ["llama.pool_k_2", "llama.pool_v_2"]
    assert engine.state_names == [
        f"llama.{kind}_state_{i}" for i in (0, 1, 3)
        for kind in ("conv", "ssm")]
    assert engine.slot_state_bytes == 3 * 4 * (3 * 160 + 16 * 128) * 4
    for n in engine.state_names:
        assert n not in engine._weight_names()


def test_more_than_one_group_widens_the_convolution_rows_alone():
    """(PR 63 built what this test saw refused.)  Two groups of B and C
    are ``2 * 2 * state`` of the convolution's channels; the matrix state
    is every head's lanes, whatever the groups; groups that do not divide
    the heads are refused."""
    from paddle_tpu.models.llama import cache_spec

    def shapes(groups):
        mixer = dict(BUILDER.layer_pattern(_cfg())[0]["mixer"], groups=groups)
        return [e["shape"] for e in cache_spec(
            "llama", 1, [{"mixer": mixer}], num_slots=2, num_pages=4,
            page_tokens=PAGE, num_kv_heads=2, head_dim=16, hidden=64)]

    assert shapes(1) == [[3, 3, 8 * 16 + 2 * 16], [3, 16, 8 * 16]]
    assert shapes(2) == [[3, 3, 8 * 16 + 4 * 16], [3, 16, 8 * 16]]
    with pytest.raises(ValueError, match="groups of B and C"):
        shapes(3)


def test_the_builder_reads_the_published_keys():
    cfg = _cfg()
    model = BUILDER.model_args(cfg)
    ssd = {"kind": "ssd", "heads": 8, "head_dim": 16, "state": 16,
           "groups": 1, "conv": 4, "conv_bias": True}
    common = {"window": None, "rope": False, "ffn": "dense",
              "attn_precision": "highest"}
    assert model["layer_pattern"] == [
        dict(common, mixer=ssd if k == "mamba" else "attention")
        for k in PERIOD]
    assert (model["embed_scale"], model["residual_scale"],
            model["attn_scale"], model["logit_scale"], model["tie_head"],
            model["rms_norm_eps"], model["intermediate"]) \
        == (12.0, 0.22, 0.0625, 0.125, True, 1e-5, 96)
    assert "rope_base" not in model and "head_dim" not in model
    with pytest.raises(ValueError, match="mamba and attention"):
        BUILDER.layer_pattern(_cfg(layer_types=["mamba", "conv"] * 2))
    with pytest.raises(ValueError, match="no routed experts"):
        BUILDER.layer_pattern(_cfg(num_local_experts=8))


def test_decay_constants_differ_by_head_layer_and_seed(engine):
    """The program draws ``A_log`` / ``dt_bias`` / ``D`` as the family's
    code does (from the layer's name); the builder redraws the first two
    from a seed."""
    from paddle_tpu.serving import GenerationEngine

    fresh = GenerationEngine(BUILDER.model_args(_cfg()), num_slots=2,
                             max_seq_len=32, prefill_buckets=[8],
                             page_tokens=PAGE, autostart=False,
                             prefill_chunk=0, prefix_reuse=False,
                             speculate=False)
    a0 = np.asarray(fresh.scope.find_var("llama.blk0.ssd_A_log"))
    a1 = np.asarray(fresh.scope.find_var("llama.blk1.ssd_A_log"))
    dt = np.asarray(fresh.scope.find_var("llama.blk0.ssd_dt_bias"))
    assert a0.shape == (8,) and len(set(a0.tolist())) == 8
    assert not np.array_equal(a0, a1)
    assert (np.exp(a0) >= 1).all() and (np.exp(a0) < 16).all()
    softplus = np.log1p(np.exp(dt))
    assert (softplus > 9e-4).all() and (softplus < 0.11).all()
    assert np.array_equal(
        np.asarray(fresh.scope.find_var("llama.blk0.ssd_D")), np.ones(8))
    seeded = np.asarray(engine.scope.find_var("llama.blk0.ssd_A_log"))
    assert not np.array_equal(seeded, a0)
    BUILDER.seed_delta_gates(fresh.scope, _cfg(), 11)
    assert np.array_equal(
        np.asarray(fresh.scope.find_var("llama.blk0.ssd_A_log")), seeded)
    assert fresh.scope.find_var("llama.head.w") is None      # tied


def _uncached(cfg, model=None, seed=3, S=70):
    """``build_llama_forward``'s logits [2, S, V] on seeded weights, and
    the reference's parameters of the same scope."""
    from paddle_tpu.models.llama import build_llama_forward

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        _, fetches = build_llama_forward(
            2, S, name="llama", attn_impl="xla",
            **(model or BUILDER.model_args(cfg)))
    exe, scope = pt.Executor(), pt.Scope()
    exe.run(startup, scope=scope)
    BUILDER.seed_delta_gates(scope, cfg, seed)
    ids = np.random.default_rng(0).integers(1, 97, (2, S))
    logits, = exe.run(main, feed={"input_ids": ids.astype("int64")},
                      fetch_list=[fetches["logits"]], scope=scope)
    return ids, np.asarray(logits), REF.params_from_scope(scope, cfg, "llama")


def _off(logits, want):
    return float(np.abs(logits - want).max() / np.abs(want).max())


def test_uncached_forward_is_the_reference():
    cfg = _cfg()
    ids, logits, params = _uncached(cfg)
    for b in range(2):
        want = np.asarray(REF.forward(params, ids[b].astype("int32"), cfg))
        assert _off(logits[b], want) < TOL


@pytest.mark.parametrize("left_out", [
    {"embed_scale": 1.0}, {"residual_scale": 1.0}, {"logit_scale": 1.0},
    {"attn_scale": None},      # 16 ** -0.5, the usual softmax scale
    {"tie_head": False}])
def test_a_multiplier_left_out_is_not_the_reference(left_out):
    """Each of the family's four multipliers (and the tied head) reaches
    the program: built without one, it is off the reference by far more
    than the tolerance."""
    cfg = _cfg()
    model = dict(BUILDER.model_args(cfg), **left_out)
    ids, logits, params = _uncached(cfg, model)
    want = np.asarray(REF.forward(params, ids[0].astype("int32"), cfg))
    assert _off(logits[0], want) > 16 * TOL


def test_prefill_then_cached_decode_in_a_reused_slot_between_neighbours():
    """Slots 0 and 1 decode all the while; slot 2 serves a request, is
    left, and takes the compared ones: the paged prefill and eight cached
    decode steps are the reference's full forward (a reused slot starts
    from zero), and so is a prompt of more than one chunk of the scan."""
    cfg = _cfg()
    eng = _engine(cfg)
    w0 = stat_get("serving_slot_state_writes")
    d0 = stat_get("serving_ssm_state_steps")
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
    assert all(len(r["tokens"]) == 60 for r in rest)
    for prompt, r in res.values():
        assert _off_reference(eng, cfg, prompt, r) < TOL
    for f, r in zip((50, 51), rest):
        assert _off_reference(eng, cfg, _prompt(f, 9 + f - 50), r) < TOL
    assert counters["slot_state_writes"] == 5
    assert stat_get("serving_slot_state_writes") == w0 + 5
    # every rider of every step moved three layers' states on
    assert counters["ssm_state_steps"] % 3 == 0
    assert counters["ssm_state_steps"] >= 3 * (2 * 59 + 5 + 8 + 8)
    assert counters["delta_state_steps"] == 0
    assert stat_get("serving_ssm_state_steps") \
        == d0 + counters["ssm_state_steps"]


def test_a_dead_slots_state_is_untouched_and_the_trash_row_takes_the_warm_up():
    """Every row of both states is set to 7: the warm-up's prefills (no
    real row: a zero state) write row ``num_slots`` alone, and a request
    in slot 0 leaves slots 1 and 2, which ride its steps dead, as they
    were."""
    import jax.numpy as jnp

    eng = _engine()
    names = ("llama.ssm_state_0", "llama.conv_state_0")
    try:
        for name in names:
            eng.scope.set_var(name, jnp.full(
                eng.scope.find_var(name).shape, 7.0, jnp.float32))
        eng.warmup()
        for name in names:
            state = np.asarray(eng.scope.find_var(name))
            assert (state[:3] == 7.0).all() and not state[3].any()
        eng.generate(_prompt(90, 20), 5, timeout=300)
        after = [np.asarray(eng.scope.find_var(name)) for name in names]
    finally:
        eng.close()
    for state in after:
        assert not (state[0] == 7.0).all()
        assert (state[1:3] == 7.0).all() and not state[3].any()


def test_spans_say_what_the_scan_covered():
    from paddle_tpu import telemetry
    from paddle_tpu.ops.ssd_ops import CHUNK

    eng = _engine()
    try:
        eng.generate(_prompt(41, 70), 4, timeout=300)
        spans = [s for s in telemetry.get_spans() if s.end is not None]
    finally:
        eng.close()
    prefill = [s for s in spans if s.name == "generation/prefill"][-1]
    # 70 tokens in the rung of 192: the rung's chunks, those behind the end
    assert prefill.attrs["state_written"] == 1
    assert prefill.attrs["scan_tokens"] == 70
    assert prefill.attrs["scan_chunks"] == -(-192 // CHUNK)
    assert prefill.attrs["scan_pad_chunks"] \
        == -(-192 // CHUNK) - -(-70 // CHUNK)
    steps = [s for s in spans if s.name == "generation/decode_step"
             and "state_slots" in s.attrs]
    assert steps and all(s.attrs["state_slots"] == 1 for s in steps[-3:])
    assert all(s.attrs["live_positions"] >= 71 for s in steps[-3:])


@pytest.mark.parametrize("fault", ["state_not_written", "tail_not_written"])
def test_a_planted_fault_is_not_within_tolerance(fault, monkeypatch):
    """A prefill that leaves a slot's state-space state (or the rows of
    its convolution) unwritten reads far outside the tolerance the sound
    program sits well inside: the slot decodes on from what its last
    tenant left."""
    from paddle_tpu.ops.registry import get_op_def

    cfg = _cfg()
    write = get_op_def("slot_state_write")
    real = write.lower
    skipped = 128 if fault == "state_not_written" else 160   # channels

    def lower(ctx, op):
        if ctx.get_input(op, "State").shape[-1] == skipped:
            return ctx.set_output(op, "StateOut", ctx.get_input(op, "State"))
        return real(ctx, op)

    monkeypatch.setattr(write, "lower", lower)
    eng = _engine(cfg)
    try:
        eng.generate(_prompt(70, 25), 12, timeout=300)   # used and left
        prompt = _prompt(71, 19)
        res = eng.generate(prompt, 9, timeout=300)
    finally:
        eng.close()
    assert res["slot"] == 0
    assert _off_reference(eng, cfg, prompt, res) > 16 * TOL


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_reuse": True}, "prefix_reuse"),
    ({"prefill_chunk": 8}, "prefill_chunk > 0"),
    ({"speculate": True}, "speculate")])
def test_what_walks_pages_only_is_refused_for_state_space_state(kw, reason):
    with pytest.raises(ValueError, match="slot state") as e:
        _engine(**kw)
    assert reason in str(e.value)


def test_the_uncached_prefill_returns_both_states():
    from paddle_tpu.models.llama import build_llama_prefill

    main, startup = pt.Program(), pt.Program()
    startup._is_startup = True
    with pt.program_guard(main, startup):
        feeds, fetches = build_llama_prefill(
            2, 8, name="llama", attn_impl="xla",
            **BUILDER.model_args(_cfg()))
    assert feeds == ["input_ids", "last_pos"]
    kept = sorted(k for k in fetches if k[:2] in ("k_", "v_", "st", "ss"))
    assert kept == ["k_2", "ssm_state_0", "ssm_state_1", "ssm_state_3",
                    "state_0", "state_1", "state_3", "v_2"]
    assert tuple(fetches["state_0"].shape) == (2, 3, 160)
    assert tuple(fetches["ssm_state_0"].shape) == (2, 16, 128)


def test_delta_and_state_space_layers_in_one_model_are_refused():
    from paddle_tpu.serving import GenerationEngine

    model = BUILDER.model_args(_cfg())
    model["layer_pattern"][3] = {
        "mixer": {"kind": "gated_delta", "key_heads": 4, "value_heads": 4,
                  "key_dim": 8, "value_dim": 16, "conv": 4},
        "rope": False}
    with pytest.raises(ValueError, match="one scan's chunks"):
        GenerationEngine(model, num_slots=2, max_seq_len=64,
                         prefill_buckets=[16], page_tokens=PAGE,
                         prefill_chunk=0, prefix_reuse=False,
                         speculate=False, autostart=False)
