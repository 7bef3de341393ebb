"""Llama-style decoder LM (config 5 of BASELINE.json: Llama-2-7B, DyGraph
DP + recompute — stretch the fluid-era API to a modern LLM).

Architecture: pre-RMSNorm, fused QKV with GQA, RoPE, causal flash
attention (pallas / ring under sp), SwiGLU MLP, untied LM head.

TPU-first notes:
  * attention via the flash_attention op — pallas kernel on one chip
    and per `dp` / `mp` shard of a mesh, ring attention when the
    sequence is sharded over `sp`;
  * all projections are single large matmuls (fused QKV, fused gate+up)
    to keep the MXU busy;
  * weights stay fp32 in the scope; AMP lowers matmuls to bf16.

Decode fast path (the generation serving workload): ``llama_block``
also runs in two KV-cache modes —

  * ``collect_kv=True`` (prefill): the post-RoPE, pre-GQA-expansion
    K/V of the whole prompt come back as extra outputs, so one forward
    populates a decode cache in one shot;
  * ``kv_cache=(pool_k, pool_v)`` + ``positions`` + ``block_table``
    (cached decode): the block consumes persistent page-pool Variables,
    scatters the step's fresh K/V into the pages the block table names
    (``kv_pool_write`` — the op's output aliases the pool var, so the
    executor donates the buffer and XLA updates it in place in HBM) and
    attends the new token over the slot's pages
    (``paged_decode_attention``) — O(1) work per token instead of
    O(n²) over the prefix.

With an explicit ``name`` prefix every parameter gets a deterministic
name, so the train/full-forward, prefill, and decode programs built in
one process bind the *same* scope weights (``tests/test_generation.py``
asserts cached decode logits are bit-exact against the uncached full
forward).
"""
from __future__ import annotations

import functools

from .. import layers, telemetry


def _program_build(kind, bucket_at=None):
    """A program builder whose Python construction (ops appended, shapes
    inferred) is a ``startup/program_build`` span of the start-up account
    (``telemetry.py``): ``kind``, and as ``bucket`` the length the program
    is built for, its positional argument ``bucket_at`` (a prefill rung,
    a chunk's rows)."""
    def wrap(build):
        @functools.wraps(build)
        def timed(*args, **kwargs):
            attrs = {"kind": kind}
            if bucket_at is not None and bucket_at < len(args):
                attrs["bucket"] = args[bucket_at]
            with telemetry.startup_span("startup/program_build", **attrs):
                return build(*args, **kwargs)
        return timed
    return wrap

# One layer of the per-layer pattern.  A model's ``layer_pattern`` is a
# list of such dicts (keys left out take these values) that tiles over
# the depth: layer i runs ``layer_pattern[i % len(layer_pattern)]``.
#   window: None (every earlier token) or W: token i attends j with
#           i - W < j <= i (the window counts the token itself)
#   rope:   rotary embeddings on q and k, or none at all (NoPE);
#           "rope_interleave": True rotates the pairs (2i, 2i + 1)
#           (GPT-J's layout), not (i, i + D / 2)
#   ffn:    "dense" (SwiGLU of width ``intermediate``) or a dict
#           {"experts": E, "top_k": k, "width": I, "activation": "relu",
#            "route_from": "raw"}: dropless top-k gated experts
#           (``activation`` "relu" or "silu") routed from the layer's
#           RAW input, before its first norm and its attention, or with
#           "route_from": "normed" from what the experts read: the
#           normed post-attention stream.  Further keys, as
#           ``parallel/moe.py`` ``route_top_k`` reads them: "score"
#           ("softmax", or "sigmoid": each expert scored on its own),
#           "expert_bias" (True: a learned [E] float32 bias moves the
#           choice, never the weights), "norm_topk" (True), "route_scale"
#           (1.0), "n_group" / "topk_group" (group-limited selection over
#           softmax scores: the E experts in n_group groups of consecutive
#           indices, a token's experts out of its topk_group best groups).
#           "held": (first, count): this chip holds that range of
#           the E experts, its share of an expert-parallel group: the
#           router scores all E, only the held experts' pairs are
#           multiplied and their part of the sum goes on (no exchange, and
#           nothing in the absent chips' stead).  "shared_width": I adds a
#           shared expert, a SwiGLU of that width that every row goes
#           through, beside the routed ones; "shared_scale": c multiplies
#           its output (n shared experts of width w that are AVERAGED are
#           one SwiGLU of width n w at c = 1 / n).  "gated": False makes
#           the routed experts AND the shared one TWO matrices without a
#           gate, ``W2 relu(W1 u)^2`` ("activation" "relu2", the squared
#           ReLU, and no other; the shared expert's are
#           ``.moe.shared_up.w`` / ``.moe.shared_down.w``).  "latent": R
#           puts the routed experts in a latent row of that width: the
#           router reads the full row, ``u = h W_down`` [R] goes through
#           the dispatch, the experts ([R, I] and [I, R]) and the combine,
#           and their weighted sum comes up again, ``r W_up``, once a row;
#           the shared expert stays at full width.  "zero_experts": Z
#           makes the LAST Z of the router's E outputs identity
#           ("zero-computation") experts: scored and picked as any other
#           (one of the token's top_k places, its routing weight), no
#           weights, no matmul, the row gets ``(sum of their weights) * u``
#           added once; the real experts are 0 .. E - Z - 1 and "held" lies
#           among them; with "expert_bias" a "softmax" router chooses by
#           ``softmax + bias`` and weighs by the unbiased softmax over all
#           E.  Or None: the layer has
#           NO second half (a mixer alone: ``x = x + mixer(norm(x))``, one
#           norm ``.ln1``, one residual add)
#   branch: None, or such a dict of routed experts BESIDE a dense ``ffn``
#           (shortcut-connected experts; ``norm`` "pre" only): the experts
#           read ``u = norm(x; .ln2)``, the rows the dense FFN reads, and
#           what they give, ``s = MoE(u)``, does NOT join the stream here:
#           it is carried past this layer's FFN and the next layers' mixers,
#           cache writes and FFNs to the first layer with
#   join:   True: ``x = x + mixer(..); x = x + ffn(norm(x)) + s``, the
#           carried branch joins after this layer's FFN (under
#           ``residual_scale`` like any sublayer's output).  A published
#           layer of two attention sublayers, two dense FFNs and one expert
#           branch is two pattern layers, the first with "branch", the second
#           with "join"; a branch that never joins, a join with nothing
#           carried and a second branch before the first has joined are
#           refused.  The branch's parameters are the routed FFN's
#           (``.moe.*`` of the layer it leaves) and it counts as that
#           layer's expert layer (``expert_layers``)
#   mixer:  "attention" (q, k, v, RoPE, pages) or a dict {"kind": "conv",
#           "L_cache": L, "bias": False}: a gated short convolution,
#           ``[B, C, u] = split3(h W_in)``, ``y = (C * conv_L(B * u))
#           W_out``, depthwise and causal over L taps.  Such a layer has
#           no q, k, v, no RoPE and no pages: its cache is the last L - 1
#           rows of ``B * u``, per slot (``cache_spec``).  Or a dict
#           {"kind": "gated_delta", "key_heads": H, "value_heads": H,
#           "key_dim": Dk, "value_dim": Dv, "conv": L, "neg_eigval":
#           True}: gated delta-rule linear attention
#           (:func:`_gated_delta_mixer`), whose cache is two states a
#           slot: the last L - 1 rows that its convolution over q | k | v
#           saw, and a matrix [H, Dk, Dv] that every token moves on.
#           "value_heads" may be a multiple r of "key_heads": key head j
#           then serves value heads r j .. r j + r - 1, each with a state,
#           a decay and a beta of its own (state [Hv, Dk, Dv]).
#           With "decay": "channel" the layer is Kimi Delta Attention: the
#           log decay is a vector a head, one value a key channel, from a
#           low-rank projection of width "decay_rank"; "gate": "sigmoid"
#           with "gate_rank" makes the output gate ``sigmoid(h W_down
#           W_up)`` (the default: ``silu(h W_gate)``, full rank); "gate":
#           "sigmoid" WITHOUT "gate_rank" is the full-rank ``gate_scale *
#           sigmoid(h W_gate)`` ("gate_scale" 1.0).  Or a dict {"kind":
#           "ssd", "heads": H, "head_dim": P, "state": N, "groups": 1,
#           "conv": L, "conv_bias": True}: a state-space duality (Mamba-2)
#           layer (:func:`_ssd_mixer`), whose cache is two states a slot:
#           the last L - 1 rows that its convolution over x | B | C saw,
#           and a matrix of N state rows over all H * P channels that every
#           token decays by one number a head and writes ``B (dt x)^T``
#           into (``ops/ssd_ops.py`` says how it lies).  "groups": G > 1
#           gives B and C G groups of N ([G, N] each, 2 G N of the
#           convolution's channels): head h reads group h // (H / G)'s, and
#           the gated norm is over each group's H P / G channels apart.
#           Or None: the layer has NO mixer (an FFN alone: ``x = x +
#           ffn(norm(x))``, one norm ``.ln1``, one residual add, no cache)
#   mla:    None, or a dict that makes the attention layer LATENT
#           (:func:`_mla_mixer`): {"q_rank": Rq, "kv_rank": C, "nope_dim":
#           dn, "rope_dim": dr, "v_dim": dv, "scale": the softmax scale
#           (default (dn + dr) ** -0.5, times YaRN's ``mscale_all_dim``
#           factor squared where "yarn" has that key: ``_mla_scale``),
#           "interleave": rotate pairs (2i, 2i + 1), "yarn": None or
#           ``layers.rope``'s dict, "q_norm_scale" / "kv_norm_scale": what
#           ``norm(h W_qa)`` and ``norm(c_kv)`` are multiplied by (default
#           1; ``k_r`` is not; the cached row holds the scaled ``c_kv``)}:
#           queries through a low-rank pair with a norm between, ONE latent
#           ``c_kv`` [C] and one rotated key ``k_r`` [dr] a token shared
#           by all heads, which are all that is cached (``cache_spec``
#           kind "latent_pages"); the model's ``num_kv_heads`` and
#           ``head_dim`` are not read by such a layer
#   swiglu_limit: None, or L: every SwiGLU of the layer (dense, routed,
#           shared) is ``silu(min(gate, L)) * clip(up, -L, L)``
#   attn_gate: True multiplies the attention's output, before its output
#           projection, by ``sigmoid(h W_g)``, ``W_g`` [hidden, heads *
#           head_dim], elementwise
#   attn_precision: None (the prefill attention kernel's two products at
#           the backend's default: a TPU rounds float32 operands to
#           bfloat16) or "highest" (operands whole, as the paged decode
#           kernel and the matmuls take float32), whatever the mask
DEFAULT_LAYER = {"window": None, "rope": True, "ffn": "dense",
                 "attn_precision": None, "mixer": "attention",
                 "attn_gate": False, "mla": None, "swiglu_limit": None,
                 "rope_interleave": False, "branch": None, "join": False}


def layer_spec(layer_pattern, i):
    """Layer ``i``'s entry of the pattern, defaults filled in."""
    if not layer_pattern:
        return DEFAULT_LAYER
    return dict(DEFAULT_LAYER, **layer_pattern[i % len(layer_pattern)])


# What a serving program is declared in (``serving_dtype``): bfloat16 where
# every layer of the model is of a kind whose bfloat16 form exists, and the
# kinds that have one are listed HERE and nowhere else: a pattern key and
# the values of it that do.  A key left out (rope, rope_interleave) changes
# no kind.  The next kind to get a bfloat16 form (ROADMAP S4) adds its value
# and its kernels; a value that is a dict (a routed FFN, a mixer with state)
# is matched whole, so naming one router's experts admits no other's.
BFLOAT16_LAYER_KINDS = {
    "mixer": ("attention",),     # q, k, v over K/V pages ...
    "window": (None,),           # ... in the full pool alone,
    "mla": (None,),              # ... whole heads, not a latent row
    "ffn": ("dense",),           # a dense SwiGLU
    "branch": (None,), "join": (False,),
    "attn_gate": (False,), "swiglu_limit": (None,),
    "attn_precision": (None,),
}


def serving_dtype(model):
    """The dtype a serving engine's programs are declared in, from the
    model it is handed (the engine's ``model`` dict): ``"bfloat16"`` where
    every layer is of :data:`BFLOAT16_LAYER_KINDS`, generation is one token
    a step (no ``block_diffusion``) and the norms are RMS norms (their
    weight is float32 whatever the row's dtype; a LayerNorm's is created in
    the row's); ``"float32"`` otherwise."""
    if model.get("block_diffusion") or model.get("norm_kind", "rms") != "rms":
        return "float32"
    pattern = model.get("layer_pattern")
    for i in range(len(pattern) if pattern else 1):
        layer = layer_spec(pattern, i)
        if any(layer[key] not in kinds
               for key, kinds in BFLOAT16_LAYER_KINDS.items()):
            return "float32"
    return "bfloat16"


def state_layers(layer_pattern, num_layers):
    """Indices of the layers that keep slot state, not pages: those whose
    mixer is a gated short convolution, the gated delta rule or a
    state-space (SSD) layer."""
    return [i for i in range(num_layers)
            if layer_spec(layer_pattern, i)["mixer"] not in ("attention",
                                                              None)]


def _delta_dims(mixer):
    """A gated-delta mixer's ``(value heads: one state each, key_dim,
    value_dim, channels of its convolution: q | k | v, key heads)``."""
    key_heads, heads = int(mixer["key_heads"]), int(mixer["value_heads"])
    if heads % key_heads:
        raise ValueError(
            f"gated_delta mixer: {heads} value heads over {key_heads} key "
            f"heads is not built (value heads a multiple of key heads)")
    dk, dv = int(mixer["key_dim"]), int(mixer["value_dim"])
    return heads, dk, dv, key_heads * 2 * dk + heads * dv, key_heads


def _ssd_dims(mixer):
    """A state-space mixer's ``(heads, head_dim, state rows, channels of
    its convolution: x | B | C, groups of B and C)``."""
    heads, p, n = (int(mixer[k]) for k in ("heads", "head_dim", "state"))
    groups = int(mixer.get("groups", 1))
    if groups < 1 or heads % groups:
        raise ValueError(f"ssd mixer: {groups} groups of B and C do not "
                         f"divide {heads} heads")
    return heads, p, n, heads * p + 2 * groups * n, groups


def window_layers(layer_pattern, num_layers):
    """Indices of the attention layers whose attention is a sliding
    window."""
    return [i for i in range(num_layers)
            if layer_spec(layer_pattern, i)["mixer"] == "attention"
            and layer_spec(layer_pattern, i)["window"] is not None]


def cache_spec(name, num_layers, layer_pattern=None, *, num_slots,
               num_pages, page_tokens, num_kv_heads, head_dim, hidden,
               num_window_pages=None, dtype="float32"):
    """What a decoder keeps between steps, layer by layer: the one
    description the program builders declare their persistable state
    from and the serving engine allocates from.  A list of ``{"name",
    "layer", "kind", "shape", "dtype"}``: the page pools are ``dtype``
    (the program's, :func:`serving_dtype`), slot state is float32 whatever
    the program's.  Kinds:

    * ``"pages"`` / ``"window_pages"``: an attention layer's K and V page
      pools (two entries, K first), ``ops/decode_ops.py`` ``pool_shape``
      of the full or the window pool's page count;
    * ``"latent_pages"``: a latent (``mla``) attention layer's ONE pool
      ``<name>.pool_c_<i>`` ``[num_pages, 1, page_tokens, ROW]``, a row
      ``[c_kv | k_r]`` a token padded to whole lane tiles
      (``ops/latent_attention_ops.py`` ``latent_pool_shape``); pages are
      allocated, mapped and released as the K and V pools' are;
    * ``"slot_state"``: state a slot that is not pages, row ``num_slots``
      the trash row a warm-up writes.  A conv layer has one, its last
      ``L_cache - 1`` gated inputs: ``<name>.conv_state_<i>`` ``[num_slots
      + 1, L_cache - 1, hidden]``.  A gated-delta layer has two: the last
      ``conv - 1`` rows its convolution saw, ``<name>.conv_state_<i>``
      ``[num_slots + 1, conv - 1, key_heads * 2 * key_dim + value_heads * value_dim]``,
      then the delta state ``<name>.delta_state_<i>`` ``[num_slots + 1,
      value_heads, key_dim, value_dim]``.  A state-space (SSD) layer has
      two: ``<name>.conv_state_<i>`` ``[num_slots + 1, conv - 1, heads *
      head_dim + 2 * groups * state]``, then ``<name>.ssm_state_<i>``
      ``[num_slots + 1, state, heads * head_dim]``.

    A layer without a mixer (an FFN alone) keeps nothing."""
    from ..ops.decode_ops import pool_shape
    from ..ops.latent_attention_ops import latent_pool_shape

    windowed = window_layers(layer_pattern, num_layers)
    spec = []
    for i in range(num_layers):
        mixer = layer_spec(layer_pattern, i)["mixer"]
        if mixer is None:
            continue
        if mixer != "attention":
            if mixer["kind"] == "conv":
                shapes = {"conv_state": [int(mixer["L_cache"]) - 1, hidden]}
            elif mixer["kind"] == "ssd":
                heads, p, n, channels, _ = _ssd_dims(mixer)
                shapes = {"conv_state": [int(mixer["conv"]) - 1, channels],
                          "ssm_state": [n, heads * p]}
            else:
                heads, dk, dv, channels, _ = _delta_dims(mixer)
                shapes = {"conv_state": [int(mixer["conv"]) - 1, channels],
                          "delta_state": [heads, dk, dv]}
            spec += [{"name": f"{name}.{what}_{i}", "layer": i,
                      "kind": "slot_state", "shape": [num_slots + 1] + shape,
                      "dtype": "float32"}
                     for what, shape in shapes.items()]
            continue
        mla = layer_spec(layer_pattern, i)["mla"]
        if mla:
            if i in windowed:
                raise ValueError("a latent (mla) attention layer under a "
                                 "sliding window is not built")
            spec.append({"name": f"{name}.pool_c_{i}", "layer": i,
                         "kind": "latent_pages",
                         "shape": latent_pool_shape(
                             num_pages, page_tokens, int(mla["kv_rank"]),
                             int(mla["rope_dim"])), "dtype": dtype})
            continue
        kind = "window_pages" if i in windowed else "pages"
        shape = pool_shape(num_window_pages if i in windowed else num_pages,
                           num_kv_heads, page_tokens, head_dim)
        spec += [{"name": f"{name}.pool_{kv}_{i}", "layer": i, "kind": kind,
                  "shape": shape, "dtype": dtype} for kv in ("k", "v")]
    return spec


def _cache_vars(block, spec, layer):
    """Layer ``layer``'s persistable cache variables, declared from its
    entries of :func:`cache_spec` (K and V pools, the one latent pool,
    or the layer's one or two states)."""
    return tuple(block.create_var(
        name=e["name"], persistable=True, shape=e["shape"],
        dtype=e["dtype"], stop_gradient=True)
        for e in spec if e["layer"] == layer)


def routed_ffn(layer):
    """A pattern layer's routed experts: its ``ffn`` where that is a dict,
    else its ``branch`` (None: the layer routes nothing)."""
    ffn = layer["ffn"]
    return ffn if ffn not in ("dense", None) else layer.get("branch")


def expert_layers(layer_pattern, num_layers):
    """Indices of the layers that route over experts: as their FFN, or as
    a branch beside it."""
    return [i for i in range(num_layers)
            if routed_ffn(layer_spec(layer_pattern, i)) is not None]


def _all_joined(carry):
    """A model's layers are through: a branch still carried never joined."""
    if carry:
        raise ValueError("an expert branch left the stream and no later "
                         "layer has 'join': True")


def _linear(x, size, pname=None, name=None, rows=None):
    """``x W`` over the last dim of x [B, S, K].  ``rows`` ([1] int32, in
    a whole-prompt prefill of :func:`dense_rows_run`'s rungs): how many of
    the S rows hold a token; the product stops at the segment that holds
    the last of them and the rows behind are zero, where
    :func:`dense_rows_segment` takes a product of K weight rows in a rung
    of S."""
    segment = rows is not None and dense_rows_segment(
        x.shape[1], x.shape[2], x.dtype)
    if segment:
        return layers.fc_valid_rows(x, size, rows, param_attr=pname,
                                    name=name, segment=segment)
    return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                     param_attr=pname, name=name)


# Where a whole-prompt prefill's dense products stop at the prompt's end,
# by the rule of ISSUE 65 on tools/dense_rows_microbench.py's table (a v5e,
# float32 "highest"; PERF.md section 6, PR 64 and PR 65): a (rung, segment)
# is taken where the segmented form costs no more than 5 % over the whole
# one at a full rung at every shape measured, and saves at least half a
# segment's share of the rows one segment short of it.
#  * 256-row segments in rungs of 2048 rows and more, every product (PR 64:
#    the fused SwiGLU -2.1 to +2.4 % at a full rung, -26 % at three quarters).
#  * 256-row segments in rungs of 1024 to 2047 rows: the fused SwiGLU at
#    every width measured (-2.2 to +5.0 % at a full rung of 1024, 0.75 to
#    1.06 of a segment's share saved one short), and a single product whose
#    weight has DENSE_MIN_K rows or more (-1.6 to +4.8 %, 0.82 to 1.02): a
#    loop's extra work (its [segment, N] result is copied into the rung's
#    buffer, which is filled with zeros first) goes as N and a product's as
#    K * N, so a narrow K loses (+8.4 to +16.9 % at K 1536 and 2048) and
#    stays the plain product.
#  * Nothing under 1024 rows: a rung of 512 is two segments and its single
#    products read +8 to +18 % at a full rung; 128-row segments read +2 to
#    +51 % at every rung (Mistral's SwiGLU +7 to +10 %): twice the weight
#    reads, half the rows to spread a loop's fixed 0.2 ms over.
DENSE_MIN_ROWS = 1024
DENSE_ALL_ROWS = 2048
DENSE_MIN_K = 4096
# The same rule on the same tool's table at bfloat16 operands (one MXU pass,
# float32 sums; ``--dtype bfloat16``, a v5e, Mistral's shapes; PERF.md
# section 6, PR 68): a product is five to six times shorter and a loop
# turn's fixed cost and its [segment, N] copy are what they were.
#  * 512-row segments, the fused SwiGLU alone: +2.6 % at a full rung of 2048
#    and 0.89 of a segment's share saved one short (at 1024-row segments
#    +4.1 % and 0.93, coarser; at 256-row ones +41 to +46 %: a turn reads
#    the weights again for half the rows).  No single product: q | k | v,
#    the attention's output and the FFN's halves read +7 to +23 % at a
#    full rung at every segment.
#  * From 2048 rows: a rung of 1024 is two such segments (+2.3 %, 0.92) and
#    a prompt that takes it is longer than the rung before it, so both
#    would run.
#  * A rung that is no whole number of such segments is cut into the fewest
#    EQUAL segments of at most 512 rows where those are whole sublane tiles
#    of 16 rows (3712 rows: eight of 464), so that no row is worked twice:
#    in 512-row segments the eighth turn works 384 rows again and a full
#    rung of 3712 reads +13 %, in eight of 464 the table's last reading.
#    Where no such cut exists, 512-row segments if the last one works no
#    more than 5 % of the rung again (``overshoot``), else the plain SwiGLU.
DENSE_ROWS_BFLOAT16 = {"segment": 512, "min_rows": 2048, "tile": 16,
                       "overshoot": 0.05}


def dense_rows_segment(seq_len, k=None, dtype="float32"):
    """Rows a segment of a dense product of a whole-prompt prefill rung of
    ``seq_len`` rows of ``dtype`` (the tables above; float32's is
    ``ops/math_ops.py`` ``VALID_ROW_SEGMENT`` from :data:`DENSE_MIN_ROWS`
    rows up, for a single product of ``k`` weight rows under
    :data:`DENSE_ALL_ROWS` only where k is at least :data:`DENSE_MIN_K`;
    None: a fused SwiGLU, or the rung as a whole); None where the product
    is the plain one."""
    from ..ops.math_ops import VALID_ROW_SEGMENT

    if dtype != "float32":
        low = DENSE_ROWS_BFLOAT16
        if k is not None or seq_len < low["min_rows"]:
            return None
        segment = low["segment"]
        turns = -(-seq_len // segment)
        if seq_len % turns == 0 and seq_len // turns % low["tile"] == 0:
            return seq_len // turns
        if segment * turns > (1 + low["overshoot"]) * seq_len:
            return None
        return segment
    if seq_len < DENSE_MIN_ROWS or (
            seq_len < DENSE_ALL_ROWS and k is not None and k < DENSE_MIN_K):
        return None
    return VALID_ROW_SEGMENT


def dense_rows_run(seq_len, prompt_len, dtype="float32"):
    """Rows of a whole-prompt prefill rung of ``seq_len`` rows of ``dtype``
    that its dense products multiply at a prompt of ``prompt_len`` tokens:
    whole segments (:func:`dense_rows_segment` rows) up to the one that
    holds the last token; every row of a rung the rule leaves plain.  (In a
    rung under :data:`DENSE_ALL_ROWS` the products of a narrow weight run
    every row all the same, and so do all single products of a bfloat16
    rung.)"""
    segment = dense_rows_segment(seq_len, dtype=dtype)
    if segment is None:
        return seq_len
    return min(seq_len, segment * -(-prompt_len // segment))


def _taps_fetches(taps):
    """What a program's expert layers recorded, as fetches:
    ``expert_counts`` [L_moe, E] int32 (tokens each expert got, valid
    rows only), under group-limited selection ``expert_group_rows``
    [L_moe, n_group] int32 (valid rows that kept each group) and, where
    kept, ``router_logits`` [B, L_moe, E]."""
    out = {}
    if taps.get("counts"):
        out["expert_counts"] = layers.stack(taps["counts"], axis=0)
    if taps.get("group_rows"):
        out["expert_group_rows"] = layers.stack(taps["group_rows"], axis=0)
    if taps.get("logits"):
        out["router_logits"] = layers.stack(taps["logits"], axis=1)
    return out


def _norm(x, eps, pname, kind="rms"):
    """A decoder norm over the last dim with a learned weight and no
    bias: ``kind`` "rms" (``x / rms(x)``) or "layer" (a LayerNorm: the
    mean is subtracted first)."""
    if kind == "rms":
        return layers.rms_norm(x, epsilon=eps, param_attr=pname)
    if kind != "layer":
        raise ValueError(f"norm_kind is 'rms' or 'layer', got {kind!r}")
    return layers.layer_norm(x, scale=True, shift=False,
                             begin_norm_axis=len(x.shape) - 1, epsilon=eps,
                             param_attr=pname)


def _head(x, vocab_size, name, tie_head=False, logit_scale=1.0):
    """The LM head over normed rows x [B, S, H] -> [B, S, V]: its own
    matrix ``.head.w`` [H, V], or with ``tie_head`` the embedding table
    ``.embed`` [V, H] itself, read transposed (one parameter, not two);
    times ``logit_scale`` where that is not 1.  The logits of two-byte
    rows are the product's float32 accumulator itself, not rounded to the
    rows' dtype: the sampler and a reference check read them."""
    whole = {} if x.dtype == "float32" else {"out_dtype": "float32"}
    if not tie_head:
        logits = layers.fc(x, vocab_size, num_flatten_dims=2,
                           bias_attr=False, param_attr=f"{name}.head.w"
                           if name else None, **whole)
    else:
        from ..framework.core import default_main_program

        table = default_main_program().global_block().var(f"{name}.embed")
        logits = layers.matmul(x, table, transpose_y=True, **whole)
    if float(logit_scale) != 1.0:
        logits = layers.scale(logits, scale=float(logit_scale))
    return logits


def _head_on_rows(x, rows_idx, vocab_size, name, eps, tie_head=False,
                  norm_kind="rms", logit_scale=1.0):
    """Final norm and LM head on one gathered row per batch row: x
    [B, S, H], ``rows_idx`` [B] int64 -> logits [B, V].  The head then
    costs V x H per request, not S x V x H (5 GB of float32 logits at
    8192 x 151936)."""
    batch = x.shape[0]
    rows = layers.range(0, batch, 1, dtype="int64")
    coords = layers.stack([rows, rows_idx], axis=1)          # [B, 2]
    x = layers.unsqueeze(layers.gather_nd(x, coords), [1])   # [B, 1, H]
    x = _norm(x, eps, f"{name}.ln_f", norm_kind)
    return layers.squeeze(_head(x, vocab_size, name, tie_head, logit_scale),
                          [1])


def _conv_over_state(z, kernel, conv_w, valid, conv_state, slot, live):
    """The causal depthwise convolution of z [B, S, C] in the three modes
    of a mixer that keeps its last ``kernel - 1`` rows a slot: with
    ``live`` the decode step over ``conv_state`` (moved on in place for
    live rows); with ``valid`` a prefill whose rows before the prompt's
    true length go to slot ``slot``'s state, or come back as ``tail``
    where there is no variable; else the plain whole-sequence form.
    Returns ``(c, tail)``."""
    if live is not None:
        return layers.short_conv_step(z, conv_state, live, kernel,
                                      **conv_w), None
    c = layers.short_conv(z, kernel, **conv_w)
    tail = None
    if valid is not None:
        tail = layers.short_conv_tail(z, valid, kernel - 1)
        if conv_state is not None:
            layers.slot_state_write(conv_state, tail, slot)
            tail = None
    return c, tail


def _conv_mixer(h, hidden, mixer, p, valid=None, conv_state=None,
                slot=None, live=None, rows=None):
    """The gated short-convolution mixer on normed rows h [B, S, H]:
    ``[B, C, u] = split3(h W_in)``, ``z = B * u``, ``y = (C * conv(z))
    W_out``.  Three modes, as the attention mixer has them: with
    ``conv_state`` and ``live`` the decode step (S = 1: the state's rows
    and the fresh one, the state moved on in place for live rows); with
    ``conv_state`` and ``slot`` a prefill that also leaves the rows
    before ``valid`` (the prompt's true length) as slot ``slot``'s
    state; else the plain whole-sequence form.  ``rows``: :func:`_linear`'s,
    for both projections.  Returns ``(y, tail)``:
    ``tail`` [B, L - 1, H] where a prefill has ``valid`` and no state to
    write to (the caller fetches it), else None."""
    kernel = int(mixer["L_cache"])
    conv_w = dict(param_attr=p("conv.w"),
                  bias_attr=p("conv.b") if mixer.get("bias") else None)
    bcu = _linear(h, 3 * hidden, pname=p("conv_in.w"), rows=rows)
    gate_b, gate_c, u = (layers.slice(bcu, axes=[2], starts=[j * hidden],
                                      ends=[(j + 1) * hidden])
                         for j in range(3))
    z = layers.elementwise_mul(gate_b, u)
    c, tail = _conv_over_state(z, kernel, conv_w, valid, conv_state, slot,
                               live)
    return _linear(layers.elementwise_mul(gate_c, c), hidden,
                   pname=p("conv_out.w"), rows=rows), tail


def _delta_gate_init(name, heads, channels=None):
    """``A_log`` [heads] and ``dt_bias`` [heads, or ``channels`` under a
    decay a key channel] as the family's modelling code draws them: A
    uniform in (0, 16), ``A_log = log A``; dt log-uniform in [0.001,
    0.1], ``dt_bias = dt + log(-expm1(-dt))`` (softplus's inverse).
    Drawn from the layer's name, so every program of a model gives the
    same constants (a benchmark redraws them from its seed)."""
    import zlib

    import numpy as np

    rng = np.random.default_rng(zlib.crc32((name or "").encode()))
    a = rng.uniform(1e-3, 16.0, heads)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), channels or heads))
    return (np.log(a).astype("float32"),
            (dt + np.log(-np.expm1(-dt))).astype("float32"))


def _gated_delta_mixer(h, seq_len, hidden, mixer, p, eps, valid=None,
                       states=None, slot=None, live=None, rows=None):
    """Gated delta-rule linear attention on rows h [B, S, H]:
    ``q | k | v = silu(conv(h W_qkv))`` (one causal depthwise
    convolution over all their channels), q and k L2-normalised a head
    and q scaled by ``key_dim ** -0.5``, ``beta = sigmoid(h W_b)`` (twice
    that with ``neg_eigval``), ``g = -exp(A_log) * softplus(h W_a +
    dt_bias)``, the delta rule (``ops/gated_delta_ops.py``), then ``y =
    (rmsnorm(o) * silu(h W_gate)) W_out`` with the norm over a head's
    ``value_dim``.  ``states`` is the layer's ``(conv_state,
    delta_state)`` pair, and the three modes are :func:`_conv_mixer`'s:
    with ``live`` the decode step (both states moved on in place for
    live rows); with ``slot`` a prefill that leaves both states as they
    stand after the prompt's TRUE last token in that slot's rows; else
    the whole sequence.  ``rows``: :func:`_linear`'s, for every
    projection.  Returns ``(y, tail, state)``: what a prefill
    with ``valid`` and no variables to write to leaves the caller to
    fetch, else None.

    With ``mixer["decay"] == "channel"`` (Kimi Delta Attention) the log
    decay is a vector a head: ``g = -exp(A_log) * softplus((h W_f_down)
    W_f_up + dt_bias)`` [B, S, H, Dk], ``A_log`` [H] and ``dt_bias`` [H *
    Dk], through a projection of rank ``decay_rank``; ``beta`` has its
    own matrix; and with ``mixer["gate"] == "sigmoid"`` the output gate
    is ``sigmoid((h W_g_down) W_g_up)`` of rank ``gate_rank``."""
    from ..framework.initializer import NumpyArrayInitializer

    heads, dk, dv, channels, key_heads = _delta_dims(mixer)
    kernel = int(mixer["conv"])
    per_channel = mixer.get("decay", "head") == "channel"
    conv_state, delta_state = states if states else (None, None)
    qkv = _linear(h, channels, pname=p("gdn_qkv.w"), rows=rows)
    c, tail = _conv_over_state(qkv, kernel, {"param_attr": p("gdn_conv.w")},
                               valid, conv_state, slot, live)
    c = layers.silu(c)

    def part(lo, n, d):
        t = layers.slice(c, axes=[2], starts=[lo], ends=[lo + n * d])
        return layers.reshape(t, [0, seq_len, n, d])

    q = layers.scale(layers.l2_normalize(part(0, key_heads, dk), axis=-1,
                                         epsilon=1e-6), scale=dk ** -0.5)
    k = layers.l2_normalize(part(key_heads * dk, key_heads, dk), axis=-1,
                            epsilon=1e-6)
    v = part(2 * key_heads * dk, heads, dv)
    if heads != key_heads:
        # key head j serves value heads r j .. r j + r - 1: the ops take
        # one q and one k a state, so each key head is repeated
        def per_value_head(t):
            t = layers.reshape(t, [0, seq_len, key_heads, 1, dk])
            t = layers.tile(t, [1, 1, 1, heads // key_heads, 1])
            return layers.reshape(t, [0, seq_len, heads, dk])

        q, k = per_value_head(q), per_value_head(k)
    if per_channel:
        a = _linear(_linear(h, int(mixer["decay_rank"]),
                            pname=p("gdn_f_down.w"), rows=rows),
                    heads * dk, pname=p("gdn_f_up.w"), rows=rows)
        b = _linear(h, heads, pname=p("gdn_b.w"), rows=rows)
    else:
        ab = _linear(h, 2 * heads, pname=p("gdn_ab.w"), rows=rows)
        a = layers.slice(ab, axes=[2], starts=[0], ends=[heads])
        b = layers.slice(ab, axes=[2], starts=[heads], ends=[2 * heads])
    beta = layers.sigmoid(b)
    if mixer.get("neg_eigval"):
        beta = layers.scale(beta, scale=2.0)
    a_log, dt_bias = (layers.create_parameter(
        [len(init)], "float32", name=p(what),
        default_initializer=NumpyArrayInitializer(init))
        for what, init in zip(
            ("gdn_A_log", "gdn_dt_bias"),
            _delta_gate_init(p("gdn"), heads,
                             heads * dk if per_channel else None)))
    if per_channel:
        g = layers.scale(layers.elementwise_mul(
            layers.reshape(layers.softplus(
                layers.elementwise_add(a, dt_bias)), [0, seq_len, heads, dk]),
            layers.exp(a_log), axis=2), scale=-1.0)
    else:
        g = layers.scale(layers.elementwise_mul(
            layers.softplus(layers.elementwise_add(a, dt_bias)),
            layers.exp(a_log)), scale=-1.0)
    state = None
    if live is not None:
        o = layers.gated_delta_step(q, k, v, g, beta, delta_state, live)
    else:
        # (a slot's state is all it has: a prefill starts from none)
        o, last = layers.gated_delta_chunk(q, k, v, g, beta, valid=valid)
        if delta_state is not None:
            layers.slot_state_write(delta_state, last, slot)
        elif valid is not None:
            state = last
    o = layers.rms_norm(o, epsilon=eps, param_attr=p("gdn_norm"))
    sigmoid = mixer.get("gate", "silu") == "sigmoid"
    low_rank = sigmoid and "gate_rank" in mixer
    gate = _linear(_linear(h, int(mixer["gate_rank"]),
                           pname=p("gdn_g_down.w"), rows=rows),
                   heads * dv, pname=p("gdn_g_up.w"), rows=rows) \
        if low_rank \
        else _linear(h, heads * dv, pname=p("gdn_gate.w"), rows=rows)
    gate = layers.reshape(gate, [0, seq_len, heads, dv])
    gate = layers.sigmoid(gate) if sigmoid else layers.silu(gate)
    if float(mixer.get("gate_scale", 1.0)) != 1.0:
        gate = layers.scale(gate, scale=float(mixer["gate_scale"]))
    o = layers.reshape(layers.elementwise_mul(o, gate),
                       [0, seq_len, heads * dv])
    return _linear(o, hidden, pname=p("gdn_out.w"), rows=rows), tail, state


def _ssd_init(name, heads):
    """``A_log``, ``dt_bias`` and ``D`` [heads] as the family's modelling
    code draws them: A uniform in (1, 16), ``A_log = log A``; dt
    log-uniform in [0.001, 0.1], ``dt_bias = dt + log(-expm1(-dt))``
    (softplus's inverse); D ones.  Drawn from the layer's name, so every
    program of a model gives the same constants (a benchmark redraws them
    from its seed)."""
    import zlib

    import numpy as np

    rng = np.random.default_rng(zlib.crc32((name or "").encode()))
    a = rng.uniform(1.0, 16.0, heads)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), heads))
    return {"ssd_A_log": np.log(a).astype("float32"),
            "ssd_dt_bias": (dt + np.log(-np.expm1(-dt))).astype("float32"),
            "ssd_D": np.ones(heads, "float32")}


def _ssd_mixer(h, seq_len, hidden, mixer, p, eps, valid=None, states=None,
               slot=None, live=None, rows=None):
    """A state-space duality (Mamba-2) layer on normed rows h [B, S, H]:
    ``z | xBC | dt = h W_in`` (no bias); ``x | B | C = silu(conv(xBC) +
    b)``, one causal depthwise convolution over all their channels, ``x``
    [heads, head_dim] and ``B``, ``C`` [state] shared by every head (with
    ``groups`` G > 1: [G, state] each, head h reading group h // (heads /
    G)'s); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the
    recurrence ``S <- exp(dt A) S + (dt x) B^T``, ``y = S C + D x``
    (``ops/ssd_ops.py``); then ``out = rmsnorm(y * silu(z)) W_out``, the
    norm over all ``heads * head_dim`` channels (over each group's apart)
    with one learned weight.
    ``states`` is the layer's ``(conv_state, ssm_state)`` pair and the
    three modes are :func:`_gated_delta_mixer`'s, as is what it
    returns."""
    from ..framework.initializer import NumpyArrayInitializer

    heads, hp, n, channels, groups = _ssd_dims(mixer)
    inner = heads * hp
    conv_state, ssm_state = states if states else (None, None)
    zxd = _linear(h, inner + channels + heads, pname=p("ssd_in.w"),
                  rows=rows)

    def cut(t, lo, width):
        return layers.slice(t, axes=[2], starts=[lo], ends=[lo + width])

    z, dt = cut(zxd, 0, inner), cut(zxd, inner + channels, heads)
    conv_w = {"param_attr": p("ssd_conv.w"),
              "bias_attr": p("ssd_conv.b") if mixer.get("conv_bias") else None}
    c, tail = _conv_over_state(cut(zxd, inner, channels), int(mixer["conv"]),
                               conv_w, valid, conv_state, slot, live)
    c = layers.silu(c)
    x = layers.reshape(cut(c, 0, inner), [0, seq_len, heads, hp])
    bm, cm = cut(c, inner, groups * n), cut(c, inner + groups * n,
                                            groups * n)
    if groups > 1:
        bm, cm = (layers.reshape(t, [0, seq_len, groups, n])
                  for t in (bm, cm))
    a_log, dt_bias, d = (layers.create_parameter(
        [heads], "float32", name=p(what),
        default_initializer=NumpyArrayInitializer(init))
        for what, init in _ssd_init(p("ssd"), heads).items())
    dt = layers.softplus(layers.elementwise_add(dt, dt_bias))
    a = layers.scale(layers.exp(a_log), scale=-1.0)
    state = None
    if live is not None:
        y = layers.ssd_step(x, dt, a, bm, cm, d, ssm_state, live)
    else:
        # (a slot's state is all it has: a prefill starts from none)
        y, last = layers.ssd_chunk(x, dt, a, bm, cm, d, valid=valid)
        if ssm_state is not None:
            layers.slot_state_write(ssm_state, last, slot)
        elif valid is not None:
            state = last
    y = layers.elementwise_mul(layers.reshape(y, [0, seq_len, inner]),
                               layers.silu(z))
    y = layers.rms_norm(y, epsilon=eps, param_attr=p("ssd_norm"),
                        group_size=inner // groups)
    return _linear(y, hidden, pname=p("ssd_out.w"), rows=rows), tail, state


def _residual(x, y, scale=1.0):
    """``x + scale * y``: a sublayer's output joins the stream."""
    if float(scale) != 1.0:
        y = layers.scale(y, scale=float(scale))
    return layers.elementwise_add(x, y)


def _embed(ids, vocab_size, hidden, pname, scale=1.0, dtype="float32"):
    """The token rows, times ``scale`` where that is not 1.  ``dtype``: the
    table's, and with it the residual stream's and every matrix's behind
    it (a layer creates its weights in its input's dtype; norm weights are
    float32 whatever the row's)."""
    x = layers.embedding(ids, size=[vocab_size, hidden], param_attr=pname,
                         dtype=dtype)
    if float(scale) != 1.0:
        x = layers.scale(x, scale=float(scale))
    return x


def llama_block(x, hidden, num_heads, num_kv_heads, seq_len, head_dim,
                intermediate, name=None, attn_impl="auto",
                kv_cache=None, positions=None, collect_kv=False,
                block_table=None, kv_lengths=None, rms_norm_eps=1e-6,
                rope_base=10000.0, layer=None, valid=None, taps=None,
                qk_norm=False, mask_block=None, block=False,
                conv_state=None, slot=None, live=None, norm="pre",
                norm_kind="rms", chunk_pages=False, residual_scale=1.0,
                attn_scale=None, dense_rows=None, carry=None):
    """One decoder layer. x: [B, S, H].

    ``carry``: the dict a model's layers share for what leaves the stream
    at one layer and joins it at a later one (the pattern's ``branch`` /
    ``join``): a layer with a branch puts its experts' output there, the
    layer that joins takes it out.

    ``dense_rows`` ([1] int32; a whole-prompt prefill of a long rung,
    B = 1): the rows that hold a token, at which every dense product of
    the layer stops (:func:`_linear`'s ``rows``).

    A layer whose ``mixer`` is a gated short convolution
    (:data:`DEFAULT_LAYER`) runs :func:`_conv_mixer` where the others
    run attention: ``conv_state`` is its per-slot state variable, with
    ``live`` [B] in the decode step and ``slot`` [1] (and ``valid``, the
    prompt's length) in a prefill; with ``collect_kv`` it returns ``(x,
    tail, None)``, the state rows where no variable took them.  A
    gated-delta layer (:func:`_gated_delta_mixer`) takes its two state
    variables as the pair ``conv_state`` and returns ``(x, tail, state)``,
    and so does a state-space layer (:func:`_ssd_mixer`).
    ``residual_scale`` multiplies what the mixer and the FFN add to the
    stream (``x = x + c * mixer(norm(x)); x = x + c * ffn(norm(x))``);
    ``attn_scale`` is the attention layers' softmax scale where it is not
    ``head_dim ** -0.5``.

    ``qk_norm``: q and k are RMS-normalised over ``head_dim`` with a
    learned weight each (``.q_norm`` / ``.k_norm``) before RoPE; with
    ``qk_norm="proj"`` over the whole projection (all heads' ``num_heads
    * head_dim`` at once, weights of that length) before the split into
    heads.  ``norm``: "pre" (the norms on the mixer's and the FFN's
    input), "post" (on their OUTPUT, none on their input: ``x = x +
    norm(mixer(x)); x = x + norm(ffn(x))``, weights ``.ln1`` / ``.ln2``
    still), "pre_post" (both: ``x = x + norm(mixer(norm(x)))``, four
    weights a layer, ``.ln1`` / ``.ln1_post`` / ``.ln2`` / ``.ln2_post``)
    or "parallel" (ONE norm a layer, ``.ln1``: ``h = norm(x); x = x +
    mixer(h) + ffn(h)``, both halves read the same ``h`` and are added to
    the raw ``x``).  ``norm_kind``: every norm of the residual path is
    "rms" or "layer" (:func:`_norm`; the q / k norms stay RMS).
    A layer with ``mla`` in its pattern entry is latent attention
    (:func:`_mla_mixer`): ``kv_cache`` is then its ONE pool, a 1-tuple,
    and with ``collect_kv`` it returns ``(x, row, None)``.
    ``mask_block`` (uncached and ``collect_kv`` modes): the attention
    mask is block-causal, row i admits column j iff ``j // mask_block
    <= i // mask_block`` (block diffusion's prefill).  ``block`` (with
    ``kv_cache``): the ``seq_len`` rows of a batch row are one block at
    ``positions[b]`` whose rows all attend the committed columns and
    the whole block (``paged_decode_attention`` with that many rows: a
    denoising or a commit pass of block diffusion).

    A layer whose ``mixer`` or whose ``ffn`` is None is that ONE sublayer
    alone, ``x = x + f(norm(x))`` under the one norm ``.ln1`` (``norm``
    "pre" only): an FFN alone takes and keeps no cache and returns ``(x,
    None, None)`` with ``collect_kv``.

    ``layer`` is the layer's entry of the model's pattern
    (:data:`DEFAULT_LAYER`; None is the default: full causal attention,
    RoPE, dense SwiGLU).  With routed experts ``valid`` [B] int is the
    number of real rows per batch row (for the expert counts) and
    ``taps`` a dict the layer appends its ``counts`` (and, when
    ``taps["keep_logits"]``, its router ``logits``) to.

    ``name`` prefixes every parameter deterministically (required when
    several programs must share one scope).  ``attn_impl`` feeds the
    flash_attention op's impl switch ("auto" | "xla" | pallas bools).

    Cache modes (mutually exclusive):
      * ``kv_cache=(pool_k, pool_v)`` with ``positions`` [B] int32,
        ``block_table`` [B, NP] and ``kv_lengths`` [B] — cached decode
        over block-paged pools [P, n_kv, page_tokens, D]: the step's
        K/V scatter into the slots' current pages (``kv_pool_write``)
        and x comes back with the pools updated in place.
        With ``seq_len`` 1 (the decode step) the new token attends its
        slot's live pages in place (``paged_decode_attention``: a
        Pallas kernel on a TPU, held to the reference at a tolerance;
        the gather + einsum formulation anywhere else).  ``seq_len`` > 1
        is a *prefill chunk*: S new tokens starting at ``positions[b]``
        attend the gathered logical view plus themselves causally
        (``kv_pool_gather`` -> ``chunk_attention``, under the layer's
        window where it has one).  The program's shape picks the path.
        ``chunk_pages``: the caller vouches that ``positions`` is a page
        boundary and the chunk whole pages, so its K/V go in page by
        page (``kv_pool_write(whole_pages=True)``: no pool re-laid).
      * ``collect_kv=True`` — prefill: returns ``(x, k, v)`` where
        k/v are the post-RoPE [B, n_kv, S, D] cache rows.
    """
    layer = layer or DEFAULT_LAYER
    # a layer without a window calls the attention layers exactly as
    # before the pattern existed
    win = {} if layer["window"] is None else {"window": layer["window"]}
    if attn_scale is not None:
        win = dict(win, scale=float(attn_scale))
    q_size = num_heads * head_dim
    kv_size = num_kv_heads * head_dim
    p = (lambda s: f"{name}.{s}") if name else (lambda s: None)
    x_in = x          # routed experts read the raw layer input
    pre, post = _norm_modes(norm)

    def normed(t, pname):
        return _norm(t, rms_norm_eps, p(pname), norm_kind)

    def post_normed(y):
        """The mixer's output under its own norm, where the layout has
        one (``.ln1`` under "post", ``.ln1_post`` beside the input's)."""
        return normed(y, "ln1_post" if pre else "ln1") if post else y

    h = normed(x, "ln1") if pre else x
    ffn_args = dict(ffn=layer["ffn"], p=p, rms_norm_eps=rms_norm_eps,
                    valid=valid, taps=taps, norm=norm,
                    limit=layer.get("swiglu_limit"), norm_kind=norm_kind,
                    h=h if norm == "parallel" else None,
                    residual_scale=residual_scale, rows=dense_rows,
                    branch=layer.get("branch"), join=layer.get("join"),
                    carry=carry)
    if layer["mixer"] is None or layer["ffn"] is None:
        if norm != "pre" or (layer["mixer"] is None
                             and layer["ffn"] is None):
            raise ValueError(
                f"a layer of one sublayer (mixer {layer['mixer']!r}, ffn "
                f"{layer['ffn']!r}) has that one, under norm 'pre' (got "
                f"{norm!r})")
    if layer["mixer"] is None:
        # the FFN alone, on the layer's one norm
        out = _ffn(x, x_in, hidden, intermediate, **dict(ffn_args, h=h))
        return (out, None, None) if collect_kv else out
    if layer["mixer"] != "attention":
        state = None
        if layer["mixer"]["kind"] == "conv":
            y, tail = _conv_mixer(h, hidden, layer["mixer"], p, valid=valid,
                                  conv_state=conv_state, slot=slot,
                                  live=live, rows=dense_rows)
        else:
            mixer = _ssd_mixer if layer["mixer"]["kind"] == "ssd" \
                else _gated_delta_mixer
            y, tail, state = mixer(
                h, seq_len, hidden, layer["mixer"], p, rms_norm_eps,
                valid=valid, states=conv_state, slot=slot, live=live,
                rows=dense_rows)
        out = _ffn(_residual(x, post_normed(y), residual_scale), x_in,
                   hidden, intermediate, **ffn_args)
        return (out, tail, state) if collect_kv else out
    if layer.get("mla"):
        y, row = _mla_mixer(
            h, seq_len, hidden, num_heads, layer, p, rms_norm_eps,
            rope_base, attn_impl, kv_cache=kv_cache, positions=positions,
            block_table=block_table, kv_lengths=kv_lengths,
            want_row=collect_kv, chunk_pages=chunk_pages, rows=dense_rows)
        out = _ffn(_residual(x, post_normed(y), residual_scale), x_in,
                   hidden, intermediate, **ffn_args)
        return (out, row, None) if collect_kv else out
    qkv = _linear(h, q_size + 2 * kv_size, pname=p("qkv.w"),
                  rows=dense_rows)
    q = layers.slice(qkv, axes=[2], starts=[0], ends=[q_size])
    k = layers.slice(qkv, axes=[2], starts=[q_size],
                     ends=[q_size + kv_size])
    v = layers.slice(qkv, axes=[2], starts=[q_size + kv_size],
                     ends=[q_size + 2 * kv_size])

    def heads(t, n):
        t = layers.reshape(t, [0, seq_len, n, head_dim])
        return layers.transpose(t, [0, 2, 1, 3])  # [B,n,S,D]

    if qk_norm == "proj":
        q, k = normed(q, "q_norm"), normed(k, "k_norm")
    q, k, v = heads(q, num_heads), heads(k, num_kv_heads), \
        heads(v, num_kv_heads)
    if qk_norm and qk_norm != "proj":
        q = layers.rms_norm(q, epsilon=rms_norm_eps, param_attr=p("q_norm"))
        k = layers.rms_norm(k, epsilon=rms_norm_eps, param_attr=p("k_norm"))
    if layer["rope"]:
        rot = dict(base=rope_base,
                   offset=positions if kv_cache is not None else None,
                   interleave=bool(layer.get("rope_interleave")))
        q = layers.rope(q, **rot)
        k = layers.rope(k, **rot)

    if kv_cache is not None:
        # cached decode: scatter this step's K/V into the slots' pages,
        # then attend the new token(s) over the (updated) pools — GQA
        # expansion happens inside the attention ops.  Write-before-
        # read makes the fresh rows visible (the mask admits
        # j <= positions[b] + t, which includes this step's own columns)
        cache_k, cache_v = kv_cache
        # a block's few rows a slot scatter as the one-row step's do
        form = {"per_head": True} if block else \
            {"whole_pages": True} if chunk_pages and seq_len > 1 else {}
        cache_k = layers.kv_pool_write(cache_k, k, positions,
                                       block_table, kv_lengths, **form)
        cache_v = layers.kv_pool_write(cache_v, v, positions,
                                       block_table, kv_lengths, **form)
        if seq_len == 1 or block:
            # the decode step: live pages in place.  A block's rows are
            # written before they are read, every pass: the pass whose
            # input holds no mask token leaves the K/V later blocks
            # attend, and nothing is rolled back
            attn = layers.paged_decode_attention(
                q, cache_k, cache_v, block_table, positions, **win)
        else:
            # a chunk of query rows: the gathered logical view
            gk = layers.kv_pool_gather(cache_k, block_table,
                                       head_dim=head_dim)
            gv = layers.kv_pool_gather(cache_v, block_table,
                                       head_dim=head_dim)
            attn = layers.chunk_attention(q, gk, gv, positions, **win)
    else:
        cache_k = cache_v = None
        new_k, new_v = k, v  # pre-expansion rows are what a cache stores
        if num_kv_heads != num_heads:
            # repeat_interleave-style expansion [k1,k1,..,k2,k2,..]:
            # query-head group g maps to kv head g//rep, matching
            # canonical Llama GQA (block-order tile would pair queries
            # with the wrong kv heads).
            rep = num_heads // num_kv_heads

            def expand_kv(t):
                t = layers.reshape(t, [0, num_kv_heads, 1, seq_len,
                                       head_dim])
                t = layers.tile(t, [1, 1, rep, 1, 1])
                return layers.reshape(t, [0, num_heads, seq_len,
                                          head_dim])

            k, v = expand_kv(k), expand_kv(v)
        if mask_block is not None:
            win = dict(win, mask_block=mask_block)
        if layer.get("attn_precision") is not None:
            win = dict(win, precision=layer["attn_precision"])
        if x.dtype != "float32":
            # (the kernels keep their softmax state float32 whatever the
            # operands; the einsum formulation has to be told)
            win = dict(win, softmax_float32=True)
        attn = layers.flash_attention(q, k, v, causal=True,
                                      impl=attn_impl, **win)
    attn = layers.transpose(attn, [0, 2, 1, 3])
    attn = layers.reshape(attn, [0, seq_len, q_size])
    if layer.get("attn_gate"):
        attn = layers.elementwise_mul(attn, layers.sigmoid(
            _linear(h, q_size, pname=p("attn_gate.w"), rows=dense_rows)))
    y = _linear(attn, hidden, pname=p("attn_out.w"), rows=dense_rows)
    x = _residual(x, post_normed(y), residual_scale)
    out = _ffn(x, x_in, hidden, intermediate, **ffn_args)
    if collect_kv:
        return out, new_k, new_v
    return out


def _mla_scale(mla):
    """A latent layer's softmax scale: ``mla["scale"]`` where given, else
    ``(nope + rope) ** -0.5``, times ``yarn_mscale(factor, mscale_all_dim)
    ** 2`` where its ``yarn`` dict carries ``mscale_all_dim`` (cos and sin
    carry ``mscale / mscale_all_dim`` of the same formula, which must come
    out as 1: another factor is not built)."""
    from ..ops.rope_ops import yarn_mscale

    if mla.get("scale"):
        return float(mla["scale"])
    scale = (int(mla["nope_dim"]) + int(mla["rope_dim"])) ** -0.5
    yarn = mla.get("yarn") or {}
    if yarn.get("mscale_all_dim"):
        factor, all_dim = yarn["factor"], yarn["mscale_all_dim"]
        if yarn_mscale(factor, yarn.get("mscale", all_dim)) \
                != yarn_mscale(factor, all_dim):
            raise ValueError("YaRN with mscale != mscale_all_dim (a factor "
                             "on cos and sin) is not built")
        scale *= yarn_mscale(factor, all_dim) ** 2
    return scale


def _mla_mixer(h, seq_len, hidden, num_heads, layer, p, eps, rope_base,
               attn_impl, kv_cache=None, positions=None, block_table=None,
               kv_lengths=None, want_row=False, chunk_pages=False,
               rows=None):
    """Latent attention (MLA) on normed rows h [B, S, H]: ``c_q = norm(h
    W_qa)``, ``[q_nope | q_rope] = c_q W_qb`` a head; ``[c_kv | k_r] = h
    W_kva``, ``c_kv = norm(c_kv)``; ``q_rope`` and the one ``k_r`` rotated;
    ``[k_nope | v] = c_kv W_kvb`` a head; scores ``(q_nope . k_nope +
    q_rope . k_r) * scale``, causal softmax, ``o = sum p v``; ``y = (o *
    sigmoid(h W_g)) W_o`` with ``attn_gate``.  What a token leaves behind
    is the row ``[c_kv | k_r]`` (post-norm, post-RoPE), padded to the
    pool's whole lane tiles.

    Three paths.  **Expanded** (whole sequences: the full forward and the
    prefill): keys and values of every head are made from the latent and
    ``latent_prefill_attention`` runs over them.  **Absorbed** (the
    decode step, ``kv_cache`` the layer's one pool and ``seq_len`` 1): the
    row is written, then ``latent_decode_attention`` meets the cached
    rows as they lie, with ``W_kvb`` read as ``W_UK`` and ``W_UV``.  **A
    chunk** (``kv_cache`` and ``seq_len`` > 1: S new rows of one slot at
    ``positions[0]``): the rows are written (``chunk_pages``: as whole
    pages), then ``latent_chunk_attention`` expands the slot's cached rows
    block by block through ``W_kvb`` and the chunk attends them and itself
    causally; the chunk at base 0 is the same program.  The one parameter
    ``.kv_b.w`` in all three.  ``rows``: :func:`_linear`'s, for every
    projection of the expanded path.  Returns ``(y, row)``: the
    rows [B, 1, S, ROW] with ``want_row`` (a prefill scatters them), else
    None."""
    from ..ops.latent_attention_ops import latent_pool_shape

    mla = layer["mla"]
    rank_q, rank_kv = int(mla["q_rank"]), int(mla["kv_rank"])
    dn, dr, dv = (int(mla[k]) for k in ("nope_dim", "rope_dim", "v_dim"))
    scale = _mla_scale(mla)
    rot = dict(base=rope_base, interleave=bool(mla.get("interleave")),
               yarn=mla.get("yarn"),
               offset=positions if kv_cache is not None else None)

    def normed(t, pname, scale=1.0):
        t = layers.rms_norm(t, epsilon=eps, param_attr=p(pname))
        return t if float(scale) == 1.0 else layers.scale(
            t, scale=float(scale))

    def cut(t, axis, lo, hi):
        return layers.slice(t, axes=[axis], starts=[lo], ends=[hi])

    def heads(t, n, d):
        return layers.transpose(layers.reshape(t, [0, seq_len, n, d]),
                                [0, 2, 1, 3])               # [B, n, S, d]

    q = heads(_linear(normed(_linear(h, rank_q, pname=p("q_a.w"), rows=rows),
                             "q_a_norm", mla.get("q_norm_scale", 1.0)),
                      num_heads * (dn + dr), pname=p("q_b.w"), rows=rows),
              num_heads, dn + dr)
    q_nope = cut(q, 3, 0, dn)
    q_rope = layers.rope(cut(q, 3, dn, dn + dr), **rot)
    kv_a = _linear(h, rank_kv + dr, pname=p("kv_a.w"), rows=rows)
    c_kv = normed(cut(kv_a, 2, 0, rank_kv), "kv_a_norm",
                  mla.get("kv_norm_scale", 1.0))             # [B, S, C]
    k_r = layers.rope(heads(cut(kv_a, 2, rank_kv, rank_kv + dr), 1, dr),
                      **rot)                                 # [B, 1, S, dr]
    row = None
    if want_row or kv_cache is not None:
        lanes = latent_pool_shape(1, 1, rank_kv, dr)[-1]
        row = layers.pad(
            layers.concat([layers.unsqueeze(c_kv, [1]), k_r], axis=3),
            [0, 0, 0, 0, 0, 0, 0, lanes - rank_kv - dr])
    kv_b = num_heads * (dn + dv)
    if kv_cache is not None:
        form = {"whole_pages": True} if chunk_pages and seq_len > 1 else {}
        pool = layers.kv_pool_write(kv_cache[0], row, positions,
                                    block_table, kv_lengths, **form)
        w_kvb = layers.create_parameter([rank_kv, kv_b], "float32",
                                        name=p("kv_b.w"))
        if seq_len == 1:
            attn = layers.latent_decode_attention(
                q_nope, q_rope, w_kvb, pool, block_table, positions, scale,
                dv)
        else:
            attn = layers.latent_chunk_attention(
                q_nope, q_rope, w_kvb, pool, block_table, positions,
                kv_lengths, scale, dv)
    else:
        kv = heads(_linear(c_kv, kv_b, pname=p("kv_b.w"), rows=rows),
                   num_heads, dn + dv)
        k = layers.concat([cut(kv, 3, 0, dn),
                           layers.tile(k_r, [1, num_heads, 1, 1])], axis=3)
        attn = layers.latent_prefill_attention(
            layers.concat([q_nope, q_rope], axis=3), k,
            cut(kv, 3, dn, dn + dv), scale, impl=attn_impl)
    attn = layers.reshape(layers.transpose(attn, [0, 2, 1, 3]),
                          [0, seq_len, num_heads * dv])
    if layer.get("attn_gate"):
        attn = layers.elementwise_mul(attn, layers.sigmoid(
            _linear(h, num_heads * dv, pname=p("attn_gate.w"), rows=rows)))
    return _linear(attn, hidden, pname=p("attn_out.w"), rows=rows), row


def _norm_modes(norm):
    """``(pre, post)``: whether a sublayer's input and its output are
    normed under this layout.  "parallel" norms the layer's input once,
    for both halves (``llama_block``), and no output."""
    if norm not in ("pre", "post", "pre_post", "parallel"):
        raise ValueError(f"norm is 'pre', 'post', 'pre_post' or "
                         f"'parallel', got {norm!r}")
    return norm != "post", norm in ("post", "pre_post")


def _swiglu(h, hidden, width, gate_up_name, down_name, limit=None,
            rows=None):
    """``(silu(h W_gate) * (h W_up)) W_down`` with gate | up fused; with
    ``limit`` L the gate is held under L and the up to [-L, L] first.
    ``rows``: :func:`_linear`'s; the two products and what lies between
    them then run a segment at a time, in one loop."""
    if rows is not None:
        return layers.swiglu_valid_rows(
            h, width, hidden, rows, gate_up_attr=gate_up_name,
            down_attr=down_name, limit=limit,
            segment=dense_rows_segment(h.shape[1], dtype=h.dtype))
    gate_up = _linear(h, 2 * width, pname=gate_up_name)
    gate = layers.slice(gate_up, axes=[2], starts=[0], ends=[width])
    up = layers.slice(gate_up, axes=[2], starts=[width], ends=[2 * width])
    if limit is not None:
        # (the gate is held from above alone: the clip's floor is float32's)
        gate = layers.clip(gate, -3.0e38, float(limit))
        up = layers.clip(up, -float(limit), float(limit))
    return _linear(layers.elementwise_mul(layers.silu(gate), up), hidden,
                   pname=down_name)


def _relu2_mlp(h, hidden, width, up_name, down_name, rows=None):
    """``relu(h W_up)^2 W_down``: two matrices and no gate.  ``rows``:
    :func:`_linear`'s."""
    up = layers.square(layers.relu(_linear(h, width, pname=up_name,
                                           rows=rows)))
    return _linear(up, hidden, pname=down_name, rows=rows)


def _ffn(x, x_in, hidden, intermediate, ffn, p, rms_norm_eps, valid, taps,
         norm="pre", limit=None, norm_kind="rms", h=None,
         residual_scale=1.0, rows=None, branch=None, join=False,
         carry=None):
    """The layer's second half on the post-mixer stream x: norm, dense
    SwiGLU or routed experts (``x_in``: the layer's raw input, which some
    routers read), residual.  ``norm``: where the norms sit
    (:func:`_norm_modes`; the output's is ``.ln2`` under "post",
    ``.ln2_post`` beside the input's ``.ln2``).  Under "parallel" ``h``
    is the layer's one normed input, which the mixer read too, and there
    is no ``.ln2`` (so too in a layer that is an FFN alone).  ``limit``:
    the SwiGLUs' clamp; ``residual_scale``: what the FFN's output is
    multiplied by as it joins the stream; ``rows``: :func:`_linear`'s, for
    the dense, the shared and the latent products (the routed experts
    leave padded rows out by ``valid``).  ``ffn`` None: the layer has no
    second half and x is handed back.  ``branch``: routed experts beside a
    dense ``ffn``, which read the same normed rows and whose output goes
    into ``carry`` and not onto the stream; ``join``: what ``carry`` holds
    joins the stream behind this FFN's output (the pattern's keys)."""
    if (branch or join) and (carry is None or norm != "pre"
                             or ffn != "dense"):
        raise ValueError(
            f"an expert branch leaves and joins beside a dense FFN under "
            f"norm 'pre', in a model that carries it (ffn {ffn!r}, norm "
            f"{norm!r})")
    if ffn is None:
        return x
    pre, post = _norm_modes(norm)
    if h is None:
        h = _norm(x, rms_norm_eps, p("ln2"), norm_kind) if pre else x
    clamp = {} if limit is None else {"limit": float(limit)}
    if branch:
        if "branch" in carry:
            raise ValueError("a second expert branch leaves the stream "
                             "before the first has joined")
        carry["branch"] = _routed(h, x_in, hidden, branch, p, valid, taps,
                                  clamp, rows, scope="shortcut_branch")
    if ffn == "dense":
        y = _swiglu(h, hidden, intermediate, p("gate_up.w"), p("ffn_out.w"),
                    rows=rows, **clamp)
    else:
        y = _routed(h, x_in, hidden, ffn, p, valid, taps, clamp, rows)
    if post:
        y = _norm(y, rms_norm_eps, p("ln2_post" if pre else "ln2"),
                  norm_kind)
    x = _residual(x, y, residual_scale)
    if join:
        if "branch" not in carry:
            raise ValueError("a layer with 'join' and no branch carried to "
                             "it")
        x = _residual(x, carry.pop("branch"), residual_scale)
    return x


def _routed(h, x_in, hidden, ffn, p, valid, taps, clamp, rows, scope=None):
    """The routed experts ``ffn`` (a pattern dict) on normed rows ``h``,
    with their shared expert where they have one: what :func:`_ffn` adds to
    the stream, or carries as a branch (``scope``: the name its operations
    carry in the compiled module's metadata).  ``taps`` gets the layer's
    counts and router logits; the other arguments are :func:`_ffn`'s."""
    taps = taps if taps is not None else {}
    # (a latent layer's experts read and write rows of that width:
    # down before the dispatch, up after the combine, once a row)
    latent = ffn.get("latent")
    u = _linear(h, int(latent), pname=p("moe.latent_down.w"),
                rows=rows) if latent else h
    y, counts, logits = layers.moe_routed_ffn(
        u, h if ffn.get("route_from", "raw") == "normed" else x_in,
        ffn["experts"], ffn["top_k"], ffn["width"],
        activation=ffn.get("activation", "relu"), valid=valid,
        name=p("moe"), keep_router_logits=bool(taps.get("keep_logits")),
        **{k: ffn[k] for k in ("score", "expert_bias", "norm_topk",
                               "route_scale", "held", "n_group",
                               "topk_group", "gated", "zero_experts")
           if k in ffn},
        **({"scope": scope} if scope else {}), **clamp)
    if latent:
        y = _linear(y, hidden, pname=p("moe.latent_up.w"), rows=rows)
    if int(ffn.get("n_group", 1)) > 1:
        counts, group_rows = counts
        taps.setdefault("group_rows", []).append(group_rows)
    taps.setdefault("counts", []).append(counts)
    if logits is not None:
        taps.setdefault("logits", []).append(logits)
    if ffn.get("shared_width"):
        # every row, whatever it was routed to; on every chip of an
        # expert-parallel group alike, so counted once
        shared = _swiglu(
            h, hidden, int(ffn["shared_width"]),
            p("moe.shared_gate_up.w"), p("moe.shared_down.w"),
            rows=rows, **clamp) \
            if ffn.get("gated", True) else _relu2_mlp(
                h, hidden, int(ffn["shared_width"]),
                p("moe.shared_up.w"), p("moe.shared_down.w"), rows=rows)
        if float(ffn.get("shared_scale", 1.0)) != 1.0:
            shared = layers.scale(shared,
                                  scale=float(ffn["shared_scale"]))
        y = layers.elementwise_add(y, shared)
    return y


def llama(input_ids, vocab_size=32000, hidden=4096, num_layers=32,
          num_heads=32, num_kv_heads=None, intermediate=11008,
          seq_len=2048, name=None, attn_impl="auto", head_dim=None,
          rms_norm_eps=1e-6, rope_base=10000.0, layer_pattern=None,
          qk_norm=False, mask_block=None, tie_head=False, norm="pre",
          norm_kind="rms", logit_scale=1.0, embed_scale=1.0,
          residual_scale=1.0, attn_scale=None, dtype="float32"):
    """Returns logits [B, S, V]. input_ids: [B, S] int64.

    ``head_dim`` defaults to ``hidden // num_heads`` (a model may
    publish another: q is then ``num_heads * head_dim`` wide);
    ``layer_pattern`` is described at :data:`DEFAULT_LAYER`, ``qk_norm``
    ``norm``, ``norm_kind`` and ``mask_block`` at :func:`llama_block`;
    ``tie_head`` makes the head's product read the embedding table (needs
    ``name``) and ``logit_scale`` multiplies the logits; ``dtype`` is
    :func:`build_llama_prefill`'s; ``embed_scale``
    multiplies the embedding's rows, ``residual_scale`` and ``attn_scale``
    are :func:`llama_block`'s.  The defaults build exactly the program
    they always did."""
    num_kv_heads = num_kv_heads or num_heads
    head_dim = head_dim or hidden // num_heads
    p = (lambda s: f"{name}.{s}") if name else (lambda s: None)
    x = _embed(input_ids, vocab_size, hidden, p("embed"), embed_scale,
               dtype)
    carry = {}
    for i in range(num_layers):
        x = llama_block(x, hidden, num_heads, num_kv_heads, seq_len,
                        head_dim, intermediate,
                        name=f"{name}.blk{i}" if name else None,
                        attn_impl=attn_impl, rms_norm_eps=rms_norm_eps,
                        rope_base=rope_base,
                        layer=layer_spec(layer_pattern, i),
                        qk_norm=qk_norm, mask_block=mask_block, norm=norm,
                        norm_kind=norm_kind, residual_scale=residual_scale,
                        attn_scale=attn_scale, carry=carry)
    _all_joined(carry)
    x = _norm(x, rms_norm_eps, p("ln_f"), norm_kind)
    return _head(x, vocab_size, name, tie_head, logit_scale)


def build_llama_train(batch_size=None, seq_len=2048, vocab_size=32000,
                      hidden=4096, num_layers=32, num_heads=32,
                      num_kv_heads=None, intermediate=11008, **arch):
    """Causal-LM training graph: feeds input_ids + labels [B, S].
    ``arch``: :func:`llama`'s ``head_dim`` / ``rms_norm_eps`` /
    ``rope_base`` / ``layer_pattern`` (dense FFNs only: the routed
    experts are inference-only)."""
    b = -1 if batch_size is None else batch_size
    input_ids = layers.data("input_ids", [b, seq_len], dtype="int64",
                            append_batch_size=False)
    labels = layers.data("labels", [b, seq_len], dtype="int64",
                         append_batch_size=False)
    logits = llama(input_ids, vocab_size, hidden, num_layers, num_heads,
                   num_kv_heads, intermediate, seq_len, **arch)
    loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]))
    mean_loss = layers.mean(layers.squeeze(loss, [2]))
    return ["input_ids", "labels"], {"loss": mean_loss, "logits": logits}


# ---------------------------------------------------------------------------
# Generation fast path: full-forward reference / prefill / cached decode
# ---------------------------------------------------------------------------

def build_llama_forward(batch_size, seq_len, vocab_size=32000,
                        hidden=4096, num_layers=32, num_heads=32,
                        num_kv_heads=None, intermediate=11008,
                        name="llama", attn_impl="auto", **arch):
    """Uncached full forward: feeds input_ids [B, S], fetches logits
    [B, S, V] (causal — row i depends only on tokens ≤ i, so one run
    yields every decode step's reference logits)."""
    input_ids = layers.data("input_ids", [batch_size, seq_len],
                            dtype="int64", append_batch_size=False)
    logits = llama(input_ids, vocab_size, hidden, num_layers, num_heads,
                   num_kv_heads, intermediate, seq_len, name=name,
                   attn_impl=attn_impl, **arch)
    return ["input_ids"], {"logits": logits}


@_program_build("prefill", bucket_at=1)
def build_llama_prefill(batch_size, seq_len, vocab_size=32000,
                        hidden=4096, num_layers=32, num_heads=32,
                        num_kv_heads=None, intermediate=11008,
                        name="llama", attn_impl="auto",
                        cache_slots=None, max_seq_len=None,
                        paged=None, num_pages=None, page_tokens=None,
                        head_dim=None, rms_norm_eps=1e-6,
                        rope_base=10000.0, layer_pattern=None,
                        num_window_pages=None, keep_router_logits=False,
                        qk_norm=False, mask_block=None, tie_head=False,
                        norm="pre", norm_kind="rms", logit_scale=1.0,
                        embed_scale=1.0, residual_scale=1.0,
                        attn_scale=None, stop_at_prompt=True,
                        dtype="float32"):
    """Prefill entry point: one causal forward over the (padded) prompt
    that populates a decode cache in one shot.

    ``dtype`` (every program builder's; :func:`serving_dtype` is the
    engine's choice of it): what the embedding table, every matrix, the
    residual stream and the page pools are declared in.  At "bfloat16"
    every product's operands are two bytes and its sum float32, the norms'
    weights and sums, the rotary tables and the softmax state float32, and
    the logits leave the program float32; "float32" builds the program the
    builders always built.

    In the paged mode, on a rung of :data:`DENSE_MIN_ROWS` rows or more,
    every dense product that :func:`dense_rows_segment` takes (projections,
    dense and shared FFNs; all of them from :data:`DENSE_ALL_ROWS` rows up)
    stops at the segment that holds the prompt's last token
    (``prompt_len``; :func:`dense_rows_run` rows of the rung), and the rows
    behind are zero where they were products of padding: nothing a real
    row reads, causal
    attention, the scans' ``valid``, the convolutions' tails, the head's
    one row and the pages written up to ``prompt_len`` depend on them.
    ``stop_at_prompt=False`` builds the plain products at every rung (what
    a test compares with).

    A model with layers that keep slot state (gated short convolutions,
    the gated delta rule: ``mixer`` of :data:`DEFAULT_LAYER`) takes one
    more feed in the paged mode, ``slot`` [1] int32: each such layer
    writes what it leaves behind at the prompt's TRUE last positions
    (``prompt_len``, not the bucket's: the rows its convolution saw and,
    for the delta rule, its matrix state after the last real token) as
    the whole of that slot's state (``cache_spec``; ``slot`` =
    ``cache_slots`` is the trash row).  In the other mode they come back
    as fetches ``state_<i>`` [B, L - 1, C] and ``delta_state_<i>``
    [B, heads, Dk, Dv] (a state-space layer's: ``ssm_state_<i>`` [B, N,
    heads * head_dim]).

    ``mask_block=B`` (block diffusion; the paged mode only): the forward
    runs under the block-causal mask and only commits K/V — the engine
    feeds ``prompt_len`` = the prompt's whole blocks, and the first
    generated block (with the prompt's tail at its head) is denoised by
    passes of :func:`build_llama_decode`.  There is then no head, no
    ``last_pos`` feed and no ``logits`` / ``next_token``: the fetches are
    ``rows_written`` [1] (the ``prompt_len`` fed), the expert counts and,
    with ``keep_router_logits``, ``router_logits`` [B, L_moe, S, E] of
    every row.

    A latent (``mla``) attention layer attends by the expanded path and
    writes whole pages of ``[c_kv | k_r]`` rows (post-norm, post-RoPE)
    into its ONE pool ``<name>.pool_c_<i>`` through the same block table;
    in the other mode the rows come back as the fetch ``latent_<i>``
    [B, 1, S, ROW].

    Sliding-window layers keep their pages in pools of
    their own (``num_window_pages`` pages each) behind a second feed
    ``block_table_window`` [1, NP]: the engine maps only the pages the
    window still covers after the prompt, and rows of earlier pages
    follow their zero entries to the trash page.  The final norm and
    the head run on the gathered ``last_pos`` row alone.
    Routed-expert layers add the fetch ``expert_counts`` [L_moe, E]
    and, with ``keep_router_logits``, ``router_logits`` [B, L_moe, E]
    at ``last_pos``.

    Feeds: ``input_ids`` [B, S] int64 (right-padded to the bucket) and
    ``last_pos`` [B] int64 (index of the last real token).  Fetches:
    ``logits`` [B, V] (next-token logits at last_pos) and
    ``next_token`` [B] int64 (greedy).

    Cache handling, two modes:

    * ``cache_slots``/``max_seq_len`` given (the serving engine's
      path; requires ``batch_size == 1``, ``num_pages`` and
      ``page_tokens``): the per-layer post-RoPE K/V are scattered
      **in-graph** into the decode step's block-paged pools
      ``<name>.pool_{k,v}_<i>`` through the feeds ``block_table``
      [1, NP] int32 + ``prompt_len`` [1] int32 (rows past the real
      prompt length are redirected to the trash page) — the pools are
      mutated persistable state, so the prefill step donates them
      exactly like the decode step (no K/V fetch, no host-side
      reinsert).  The forward itself is the graph of the other mode.
    * omitted: per-layer ``k_i``/``v_i`` [B, n_kv, S, D] rows come
      back as extra fetches for the caller to place.

    Because attention is causal, pad-tail rows never influence rows
    before the true length — the engine masks them out of the cache
    via per-slot positions."""
    from ..framework.core import default_main_program

    if paged not in (None, True):
        raise ValueError("the dense KV cache was removed at PR 30")
    num_kv_heads = num_kv_heads or num_heads
    head_dim = head_dim or hidden // num_heads
    input_ids = layers.data("input_ids", [batch_size, seq_len],
                            dtype="int64", append_batch_size=False)
    feeds = ["input_ids"]
    last_pos = None
    if mask_block is None:
        last_pos = layers.data("last_pos", [batch_size], dtype="int64",
                               append_batch_size=False)
        feeds.append("last_pos")
    elif cache_slots is None:
        raise ValueError("a block-causal prefill (mask_block) only "
                         "commits K/V: it needs the paged cache")
    block_table = bt_window = prompt_len = zero_pos = slot = None
    windowed = window_layers(layer_pattern, num_layers)
    has_state = bool(state_layers(layer_pattern, num_layers))
    spec = []
    if mask_block is not None and has_state:
        raise ValueError("a block-causal prefill over layers that keep "
                         "slot state is not built: their state is causal")
    if cache_slots is not None:
        if batch_size != 1:
            raise ValueError("in-graph cache insert prefills one "
                             "request at a time (batch_size must be 1)")
        if max_seq_len is None or seq_len > max_seq_len:
            raise ValueError(f"prefill bucket {seq_len} exceeds cache "
                             f"max_seq_len {max_seq_len}")
        if not num_pages or not page_tokens:
            raise ValueError("paged prefill needs num_pages and "
                             "page_tokens")
        np_slot = max_seq_len // page_tokens
        block_table = layers.data("block_table", [1, np_slot],
                                  dtype="int32", append_batch_size=False)
        prompt_len = layers.data("prompt_len", [1], dtype="int32",
                                 append_batch_size=False)
        feeds += ["block_table", "prompt_len"]
        if windowed:
            if not num_window_pages:
                raise ValueError("paged prefill with sliding-window "
                                 "layers needs num_window_pages")
            bt_window = layers.data("block_table_window", [1, np_slot],
                                    dtype="int32",
                                    append_batch_size=False)
            feeds.append("block_table_window")
        if has_state:
            slot = layers.data("slot", [1], dtype="int32",
                               append_batch_size=False)
            feeds.append("slot")
        zero_pos = layers.fill_constant([1], "int32", 0)
        spec = cache_spec(name, num_layers, layer_pattern,
                          num_slots=cache_slots, num_pages=num_pages,
                          page_tokens=page_tokens,
                          num_kv_heads=num_kv_heads, head_dim=head_dim,
                          hidden=hidden, num_window_pages=num_window_pages,
                          dtype=dtype)
    x = _embed(input_ids, vocab_size, hidden, f"{name}.embed", embed_scale,
               dtype)
    kvs = []
    taps = {"keep_logits": keep_router_logits}
    # the expert layers and those that keep slot state tell real rows
    # from the pad tail: the paged path feeds their number, the others
    # have it as last_pos + 1
    valid = prompt_len
    dense_rows = prompt_len if stop_at_prompt \
        and dense_rows_segment(seq_len, dtype=dtype) else None
    if valid is None and (has_state
                          or expert_layers(layer_pattern, num_layers)):
        valid = layers.cast(last_pos + 1, "int32")
    block = default_main_program().global_block()
    carry = {}
    for i in range(num_layers):
        caches = _cache_vars(block, spec, i)
        lspec = layer_spec(layer_pattern, i)
        state = {"conv_state": _layer_state(lspec, caches), "slot": slot} \
            if caches and lspec["mixer"] != "attention" else {}
        x, k, v = llama_block(x, hidden, num_heads, num_kv_heads,
                              seq_len, head_dim, intermediate,
                              name=f"{name}.blk{i}", attn_impl=attn_impl,
                              collect_kv=True, rms_norm_eps=rms_norm_eps,
                              rope_base=rope_base, layer=lspec,
                              valid=valid, taps=taps, qk_norm=qk_norm,
                              mask_block=mask_block, norm=norm,
                              norm_kind=norm_kind,
                              residual_scale=residual_scale,
                              attn_scale=attn_scale, dense_rows=dense_rows,
                              carry=carry, **state)
        if lspec["mixer"] is None:
            continue                 # an FFN alone leaves nothing behind
        if lspec["mixer"] != "attention":
            if not caches:
                matrix = "ssm_state" if lspec["mixer"]["kind"] == "ssd" \
                    else "delta_state"
                kvs.append((i, {"state": k} if v is None
                            else {"state": k, matrix: v}))
        elif block_table is not None:
            # paged: the prompt's K/V scatter across the slot's pages
            # from logical position 0; pad-tail rows (>= prompt_len)
            # go to the trash page, and so do a window layer's rows
            # whose page the window no longer covers.  One slot from
            # position 0: a bucket of whole pages goes in page by page
            # (a latent layer has the one pool, and the one row for it)
            for pool, t in zip(caches, (k, v)):
                layers.kv_pool_write(
                    pool, t, zero_pos,
                    bt_window if i in windowed else block_table,
                    prompt_len, whole_pages=seq_len % page_tokens == 0)
        else:
            kvs.append((i, {"latent": k} if v is None else {"k": k, "v": v}))
    _all_joined(carry)
    if mask_block is not None:
        # (kept router logits are every row's, [B, L_moe, S, E]: no row
        # is yielded, so none is picked)
        return feeds, dict({"rows_written": prompt_len + 0},
                           **_taps_fetches(taps))
    logits = _head_on_rows(x, last_pos, vocab_size, name, rms_norm_eps,
                           tie_head, norm_kind, logit_scale)
    return feeds, _prefill_fetches(logits, kvs, taps, last_pos)


def _layer_state(lspec, caches):
    """What ``llama_block`` takes as ``conv_state``: a conv layer's one
    state variable, a gated-delta or a state-space layer's pair."""
    return caches[0] if lspec["mixer"]["kind"] == "conv" else caches


def _prefill_fetches(logits, kvs, taps, last_pos):
    """A prefill's fetches from its gathered logits [B, V]: the greedy
    token, uncached K/V rows, and what the expert layers recorded (the
    router logits at ``last_pos``)."""
    fetches = {"logits": logits,
               "next_token": layers.argmax(logits, axis=-1)}  # [B] int64
    for i, rows in kvs:          # an uncached prefill's K/V or state rows
        fetches.update({f"{kind}_{i}": t for kind, t in rows.items()})
    if taps.get("logits"):
        rows = layers.range(0, int(logits.shape[0]), 1, dtype="int64")
        coords = layers.stack([rows, last_pos], axis=1)
        taps["logits"] = [layers.gather_nd(t, coords)         # [B, E]
                          for t in taps["logits"]]
    fetches.update(_taps_fetches(taps))
    return fetches


@_program_build("decode")
def build_llama_decode(num_slots, max_seq_len, vocab_size=32000,
                       hidden=4096, num_layers=32, num_heads=32,
                       num_kv_heads=None, intermediate=11008,
                       name="llama", paged=None, num_pages=None,
                       page_tokens=None, head_dim=None, rms_norm_eps=1e-6,
                       rope_base=10000.0, layer_pattern=None,
                       num_window_pages=None, keep_router_logits=False,
                       qk_norm=False, block=None, mask_id=None,
                       tie_head=False, norm="pre", norm_kind="rms",
                       logit_scale=1.0, embed_scale=1.0, residual_scale=1.0,
                       attn_scale=None, dtype="float32"):
    """Cached decode step over a fixed slot grid (``dtype``:
    :func:`build_llama_prefill`'s).

    A layer that keeps slot state (``mixer`` of :data:`DEFAULT_LAYER`)
    has no pools: a gated short convolution's ``<name>.conv_state_<i>``
    [slots + 1, L - 1, H] (``cache_spec``) is read and moved on by one
    row, a gated-delta layer's ``<name>.delta_state_<i>`` [slots + 1,
    heads, Dk, Dv] by one token (its convolution's rows too), a
    state-space layer's ``<name>.ssm_state_<i>`` [slots + 1, N, heads *
    head_dim] likewise, in place,
    for the rows ``live`` marks; a dead row's state stays as it was.  The
    engine finds those variables through ``cache_spec``; the
    ``cache_names`` returned here are the page pools alone.  A latent
    (``mla``) attention layer has ONE pool, ``<name>.pool_c_<i>``
    (``cache_spec`` kind ``latent_pages``): the step writes its row
    ``[c_kv | k_r]`` and attends by the absorbed path
    (``latent_decode_attention``), each live page read once.

    ``block=B`` (block diffusion, with ``mask_id``; full-attention
    layers only) makes a slot's rows a block of B positions at
    ``positions`` (the block's base), and one run a **pass**: feeds
    ``tokens`` [slots, B] int64 and ``masked`` [slots, B] int32 (1 = the
    position is undecided and holds ``mask_id``), ``quota`` [slots] int32
    (how many undecided positions this pass decides; 0 = a commit pass,
    whose input holds no mask) and ``fresh`` [slots] int32 (1 = ignore
    ``tokens`` / ``masked``: the slot starts a new block, all mask).  One
    program serves slots in any mix of phases.  Every pass writes the
    block's K/V at ``base .. base+B-1`` before its rows read them (all of
    the block, and the committed columns before it), so the commit pass
    leaves what later blocks attend and nothing is rolled back.  The
    logits of position i predict the token AT position i.  Fetches:
    ``tokens`` / ``masked`` [slots, B] after this pass's decisions (the
    next pass's feeds as the device holds them), ``logits`` [slots, B, V],
    and the expert layers' ``expert_counts`` / ``router_logits``
    [slots, L_moe, B, E].

    Sliding-window layers read pools of
    ``num_window_pages`` pages through a feed of their own,
    ``block_tables_window`` [slots, NP] (entries left of a slot's window
    are the trash page).  Routed-expert layers add the fetch
    ``expert_counts`` [L_moe, E] over the live slots and, with
    ``keep_router_logits``, ``router_logits`` [slots, L_moe, E].

    Feeds: ``tokens`` [slots, 1] int64 (each slot's current token) and
    ``positions`` [slots] int32 (each slot's pre-step sequence length =
    the logical offset this step writes at), ``block_tables``
    [slots, NP] int32 (NP = max_seq_len // page_tokens) and ``live``
    [slots] int32 (1 = the slot decodes this step, 0 = idle — its
    garbage write is redirected to the trash page instead of landing in
    a live page, and the expert layers leave it out of their counts).
    The per-layer block-paged pools ``<name>.pool_{k,v}_<i>``
    [num_pages, n_kv, page_tokens, D] are persistable read+written
    state — the executor donates them, so every step updates them in
    place in HBM.  Fetches: ``logits`` [slots, V] and greedy
    ``next_token`` [slots] int64.

    Returns ``(feed_names, fetches, cache_names)``."""
    from ..framework.core import default_main_program

    if paged not in (None, True):
        raise ValueError("the dense KV cache was removed at PR 30")
    if not num_pages or not page_tokens:
        raise ValueError("paged decode needs num_pages and page_tokens")
    num_kv_heads = num_kv_heads or num_heads
    head_dim = head_dim or hidden // num_heads
    rows = int(block) if block else 1
    tokens = layers.data("tokens", [num_slots, rows], dtype="int64",
                         append_batch_size=False)
    positions = layers.data("positions", [num_slots], dtype="int32",
                            append_batch_size=False)
    np_slot = max_seq_len // page_tokens
    block_tables = layers.data("block_tables", [num_slots, np_slot],
                               dtype="int32", append_batch_size=False)
    live = layers.data("live", [num_slots], dtype="int32",
                       append_batch_size=False)
    feeds = ["tokens", "positions", "block_tables", "live"]
    bt_window = None
    windowed = window_layers(layer_pattern, num_layers)
    masked = quota = None
    n_rows = live          # real rows a slot: the K/V written, the count
    if block:
        if state_layers(layer_pattern, num_layers):
            raise ValueError("a block of rows a slot over layers that "
                             "keep slot state is not built: their state "
                             "moves on one row a step")
        if windowed:
            raise ValueError("a block of rows a slot shares its columns; "
                             "sliding-window layers give each row its own")
        if mask_id is None:
            raise ValueError("block decode needs mask_id")
        masked = layers.data("masked", [num_slots, rows], dtype="int32",
                             append_batch_size=False)
        quota = layers.data("quota", [num_slots], dtype="int32",
                            append_batch_size=False)
        fresh = layers.data("fresh", [num_slots], dtype="int32",
                            append_batch_size=False)
        feeds += ["masked", "quota", "fresh"]
        tokens, masked = layers.block_begin(tokens, masked, fresh, mask_id)
        n_rows = live * rows
    if windowed:
        if not num_window_pages:
            raise ValueError("paged decode with sliding-window "
                             "layers needs num_window_pages")
        bt_window = layers.data("block_tables_window",
                                [num_slots, np_slot], dtype="int32",
                                append_batch_size=False)
        feeds.append("block_tables_window")
    gblock = default_main_program().global_block()
    spec = cache_spec(name, num_layers, layer_pattern, num_slots=num_slots,
                      num_pages=num_pages, page_tokens=page_tokens,
                      num_kv_heads=num_kv_heads, head_dim=head_dim,
                      hidden=hidden, num_window_pages=num_window_pages,
                      dtype=dtype)
    cache_names = [e["name"] for e in spec if e["kind"] != "slot_state"]
    x = _embed(tokens, vocab_size, hidden, f"{name}.embed", embed_scale,
               dtype)
    taps = {"keep_logits": keep_router_logits}
    carry = {}
    for i in range(num_layers):
        caches = _cache_vars(gblock, spec, i)
        lspec = layer_spec(layer_pattern, i)
        if lspec["mixer"] is None:
            cache = {}
        elif lspec["mixer"] != "attention":
            cache = {"conv_state": _layer_state(lspec, caches),
                     "live": live}
        else:
            cache = {"kv_cache": caches, "positions": positions,
                     "block_table": bt_window if i in windowed
                     else block_tables, "kv_lengths": n_rows}
        x = llama_block(x, hidden, num_heads, num_kv_heads, rows,
                        head_dim, intermediate, name=f"{name}.blk{i}",
                        rms_norm_eps=rms_norm_eps, rope_base=rope_base,
                        layer=lspec, valid=n_rows, taps=taps,
                        qk_norm=qk_norm, block=bool(block), norm=norm,
                        norm_kind=norm_kind, residual_scale=residual_scale,
                        attn_scale=attn_scale, carry=carry, **cache)
    _all_joined(carry)
    x = _norm(x, rms_norm_eps, f"{name}.ln_f", norm_kind)
    logits = _head(x, vocab_size, name, tie_head,
                   logit_scale)                              # [slots,1,V]
    if block:
        new_tokens, new_masked = layers.block_unmask(logits, tokens,
                                                     masked, quota)
        fetches = {"logits": logits, "tokens": new_tokens,
                   "masked": new_masked}
        fetches.update(_taps_fetches(taps))
        return feeds, fetches, cache_names
    logits = layers.squeeze(logits, [1])                     # [slots, V]
    next_token = layers.argmax(logits, axis=-1)              # [slots]
    fetches = {"logits": logits, "next_token": next_token}
    if taps.get("logits"):
        taps["logits"] = [layers.squeeze(t, [1]) for t in taps["logits"]]
    fetches.update(_taps_fetches(taps))
    return feeds, fetches, cache_names


def _chunk_forward(chunk_len, max_seq_len, num_pages, page_tokens,
                   vocab_size, hidden, num_layers, num_heads, num_kv_heads,
                   intermediate, name, head_dim=None, rms_norm_eps=1e-6,
                   rope_base=10000.0, layer_pattern=None, qk_norm=False,
                   tie_head=False, norm="pre", norm_kind="rms",
                   logit_scale=1.0, num_window_pages=None,
                   page_aligned=False, keep_router_logits=False,
                   embed_scale=1.0, residual_scale=1.0, attn_scale=None,
                   dtype="float32"):
    """The forward that the chunk and the verify programs share: C new
    tokens at ``base`` attend the slot's pages plus themselves causally.
    Returns ``(feed_names, x [1, C, H] before the final norm,
    cache_names, taps)``.

    A model with sliding-window layers (two page kinds) takes a second
    feed, ``block_table_window`` [1, NP], as its prefill and decode
    programs do: a window layer writes the chunk's K/V through it into
    its own pool (``num_window_pages`` pages) and attends, of the view
    gathered through it, the columns its window admits; entries left of
    ``base - window + 1``'s page may be the trash page.  ``page_aligned``:
    the caller vouches that ``base`` is a page boundary (``chunk_len`` a
    whole number of pages is checked), so every layer's K/V go in page by
    page and no pool is re-laid for the write.  A latent (``mla``) layer
    writes the chunk's ``[c_kv | k_r]`` rows into its one pool and attends
    the slot's latent rows expanded block by block
    (``latent_chunk_attention``).  Layers that keep slot state have no such
    program."""
    from ..framework.core import default_main_program

    if state_layers(layer_pattern, num_layers):
        raise ValueError(
            "prefill continuation (chunked prefill, prefix reuse, "
            "speculative verify) is not built for a model whose layers "
            "keep slot state: a chunk would have to start from, and a "
            "rejected draft roll back, state that is not pages")
    if page_aligned and chunk_len % page_tokens:
        raise ValueError(f"a page-aligned chunk is whole pages: "
                         f"{chunk_len} rows over pages of {page_tokens}")
    windowed = window_layers(layer_pattern, num_layers)
    if windowed and not num_window_pages:
        raise ValueError("a chunk program with sliding-window layers "
                         "needs num_window_pages")
    num_kv_heads = num_kv_heads or num_heads
    head_dim = head_dim or hidden // num_heads
    np_slot = max_seq_len // page_tokens
    chunk_ids = layers.data("chunk_ids", [1, chunk_len], dtype="int64",
                            append_batch_size=False)
    base = layers.data("base", [1], dtype="int32",
                       append_batch_size=False)
    block_table = layers.data("block_table", [1, np_slot],
                              dtype="int32", append_batch_size=False)
    ck_len = layers.data("chunk_len", [1], dtype="int32",
                         append_batch_size=False)
    feeds = ["chunk_ids", "base", "block_table", "chunk_len"]
    bt_window = None
    if windowed:
        bt_window = layers.data("block_table_window", [1, np_slot],
                                dtype="int32", append_batch_size=False)
        feeds.append("block_table_window")
    block = default_main_program().global_block()
    spec = cache_spec(name, num_layers, layer_pattern, num_slots=0,
                      num_pages=num_pages, page_tokens=page_tokens,
                      num_kv_heads=num_kv_heads, head_dim=head_dim,
                      hidden=hidden, num_window_pages=num_window_pages,
                      dtype=dtype)
    cache_names = [e["name"] for e in spec]
    x = _embed(chunk_ids, vocab_size, hidden, f"{name}.embed", embed_scale,
               dtype)
    taps = {"keep_logits": keep_router_logits}
    carry = {}
    for i in range(num_layers):
        # rope offset = base per row; the attention's validity mask
        # (j <= base + t) is exactly causal-over-prefix-plus-chunk
        x = llama_block(x, hidden, num_heads, num_kv_heads, chunk_len,
                        head_dim, intermediate, name=f"{name}.blk{i}",
                        kv_cache=_cache_vars(block, spec, i) or None,
                        positions=base,
                        block_table=bt_window if i in windowed
                        else block_table, kv_lengths=ck_len,
                        rms_norm_eps=rms_norm_eps, rope_base=rope_base,
                        layer=layer_spec(layer_pattern, i), valid=ck_len,
                        taps=taps, qk_norm=qk_norm, norm=norm,
                        norm_kind=norm_kind, chunk_pages=page_aligned,
                        residual_scale=residual_scale, attn_scale=attn_scale,
                        carry=carry)
    _all_joined(carry)
    return feeds, x, cache_names, taps


@_program_build("chunk", bucket_at=0)
def build_llama_prefill_chunk(chunk_len, max_seq_len, num_pages,
                              page_tokens, vocab_size=32000,
                              hidden=4096, num_layers=32, num_heads=32,
                              num_kv_heads=None, intermediate=11008,
                              name="llama", **arch):
    """Paged prefill *continuation*: one slice of a prompt attends the
    slot's already-populated pages plus itself causally — the program
    behind both **chunked prefill** (a long prompt feeds in
    ``FLAGS_serving_prefill_chunk`` slices interleaved with decode
    steps) and **shared-prefix reuse** (a prefix-index hit maps the
    shared pages and only the prompt tail runs here).

    Feeds: ``chunk_ids`` [1, C] int64 (right-padded slice),
    ``base`` [1] int32 (tokens already in the slot's cache = the
    logical position of the chunk's first token), ``block_table``
    [1, NP] int32, ``chunk_len`` [1] int32 (real rows; the pad tail
    writes to the trash page), ``last_off`` [1] int64 (index of the
    last real token within the chunk).  Fetches: ``logits`` [1, V] at
    ``last_off`` and greedy ``next_token`` [1] — meaningful only for
    a prompt's final chunk.  ``arch``: ``head_dim``, ``rms_norm_eps``,
    ``rope_base``, ``layer_pattern`` ... as :func:`llama` takes them, and
    :func:`_chunk_forward`'s ``num_window_pages`` (with it the feed
    ``block_table_window`` [1, NP], before ``last_off``) and
    ``page_aligned``.  With routed experts the fetch ``expert_counts``
    [L_moe, E] over the chunk's real rows and, with
    ``keep_router_logits``, ``router_logits`` [1, L_moe, E] at ``last_off``.

    Returns ``(feed_names, fetches, cache_names)``."""
    feeds, x, cache_names, taps = _chunk_forward(
        chunk_len, max_seq_len, num_pages, page_tokens, vocab_size,
        hidden, num_layers, num_heads, num_kv_heads, intermediate, name,
        **arch)
    last_off = layers.data("last_off", [1], dtype="int64",
                           append_batch_size=False)
    logits = _head_on_rows(x, last_off, vocab_size, name,
                           arch.get("rms_norm_eps", 1e-6),
                           arch.get("tie_head", False),
                           arch.get("norm_kind", "rms"),
                           arch.get("logit_scale", 1.0))
    return feeds + ["last_off"], \
        _prefill_fetches(logits, [], taps, last_off), cache_names


@_program_build("verify", bucket_at=0)
def build_llama_verify(chunk_len, max_seq_len, num_pages, page_tokens,
                       vocab_size=32000, hidden=4096, num_layers=32,
                       num_heads=32, num_kv_heads=None,
                       intermediate=11008, name="llama", **arch):
    """Speculative-decode verifier: the prefill-continuation forward
    (:func:`build_llama_prefill_chunk`) fetching EVERY row's greedy
    argmax + logits instead of one gathered row.

    The chunk carries ``[pending_token, draft_1..draft_K]`` at
    ``base`` = the slot's committed position; row ``t``'s argmax is
    the token a plain decode step would emit after committing the
    chunk's first ``t+1`` tokens, so the longest prefix with
    ``draft_{t+1} == argmax(row t)`` (plus the one bonus token row
    ``a`` yields) is exactly the plain greedy stream — bit-exact,
    tolerance 0.  Rows write their K/V into the slot's pages as a
    chunked prefill would (``chunk_len`` masks the pad tail to the
    trash page); rejected rows' garbage K/V is masked by the causal
    validity window (``j <= base + t``) and overwritten by the next
    real write at that position, so rollback is page ACCOUNTING, not
    a device-side undo.

    Feeds: ``chunk_ids`` [1, C] int64, ``base`` [1] int32,
    ``block_table`` [1, NP] int32, ``chunk_len`` [1] int32.
    Fetches: ``tokens`` [1, C] int64 (per-row greedy argmax) and
    ``logits`` [1, C, V].  The head projects ALL rows before the
    argmax — gathering hidden rows first would re-tile the
    contraction and drift ~5e-8 off the decode-step GEMM, breaking
    the acceptance contract (see :func:`build_llama_prefill`).

    Returns ``(feed_names, fetches, cache_names)``."""
    feeds, x, cache_names, _taps = _chunk_forward(
        chunk_len, max_seq_len, num_pages, page_tokens, vocab_size,
        hidden, num_layers, num_heads, num_kv_heads, intermediate, name,
        **arch)
    x = _norm(x, arch.get("rms_norm_eps", 1e-6), f"{name}.ln_f",
              arch.get("norm_kind", "rms"))
    all_logits = _head(x, vocab_size, name, arch.get("tie_head", False),
                       arch.get("logit_scale", 1.0))
    tokens = layers.argmax(all_logits, axis=-1)              # [1, C]
    return feeds, {"logits": all_logits, "tokens": tokens}, cache_names
