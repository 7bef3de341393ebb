"""BERT/ERNIE-base encoder + pretraining heads.

Parity target: the reference's ERNIE/BERT configs (PaddleNLP; in-tree
multihead precursor ops at paddle/fluid/operators/fused/multihead_matmul_op*
and bert_encoder_functor.cu). Config 3 of BASELINE.json — the north-star
throughput model.

TPU-first design notes:
  * one fused QKV projection per layer (one big MXU matmul instead of 3),
  * attention kept as batched matmuls over [B, H, S, D] — XLA maps these to
    the MXU directly; the pallas flash-attention kernel (ops/pallas) is the
    drop-in for long sequences,
  * bf16-friendly: all matmul weights created float32, AMP rewrites to bf16.
"""
from __future__ import annotations

import math

from .. import layers


def _attention(x, hidden, num_heads, seq_len, attn_bias=None, dropout=0.0,
               is_test=False, use_flash=True):
    """Multi-head self-attention. x: [-1, S, H].

    use_flash=True routes through the fused flash_attention op (pallas on
    TPU). Attention-probability dropout is folded out on that path — the
    standard trade of fused-attention kernels; output dropout is kept.
    use_flash=False keeps the unfused batched-matmul formulation (exact
    reference math incl. prob dropout, and the parity baseline in tests).
    """
    head_dim = hidden // num_heads
    qkv = layers.fc(x, size=3 * hidden, num_flatten_dims=2)  # [B,S,3H]
    if use_flash is True and dropout and not is_test:
        import warnings
        warnings.warn(
            "bert: flash attention folds out attention-probability "
            "dropout (output dropout kept); use use_flash=False for "
            "exact reference regularization", stacklevel=3)
    if use_flash is True and hidden % 128 == 0 and head_dim in (64, 128):
        # packed path: the kernel consumes the fused projection directly
        # (no [B,S,3H] <-> [B,h,S,d] transposes; measured ~2.4 GB/step of
        # layout traffic on the split-tensor path at seq-512)
        ctx = layers.flash_attention_qkv(qkv, num_heads, bias=attn_bias)
        return layers.fc(ctx, size=hidden, num_flatten_dims=2)
    if use_flash == "xla":
        # transpose-free: stay [B,S,h,d] and let the einsum op pick
        # layouts (measured faster than both the pallas kernel and the
        # explicit-transpose unfused path at S<=512 on v5e)
        qkv = layers.reshape(qkv, [0, seq_len, 3, num_heads, head_dim])
        q = layers.squeeze(
            layers.slice(qkv, axes=[2], starts=[0], ends=[1]), [2])
        k = layers.squeeze(
            layers.slice(qkv, axes=[2], starts=[1], ends=[2]), [2])
        v = layers.squeeze(
            layers.slice(qkv, axes=[2], starts=[2], ends=[3]), [2])
        import os
        prob_drop = (0.0 if os.environ.get("PT_BERT_NO_PROB_DROPOUT")
                     else dropout)
        ctx = layers.flash_attention(
            q, k, v, bias=attn_bias, impl="xla", layout="bshd",
            dropout_prob=prob_drop, is_test=is_test)   # [B,S,h,d]
        ctx = layers.reshape(ctx, [0, seq_len, hidden])
        return layers.fc(ctx, size=hidden, num_flatten_dims=2)
    qkv = layers.reshape(qkv, [0, seq_len, 3, num_heads, head_dim])
    qkv = layers.transpose(qkv, [2, 0, 3, 1, 4])  # [3,B,Hd,S,D]
    q = layers.squeeze(layers.slice(qkv, axes=[0], starts=[0], ends=[1]), [0])
    k = layers.squeeze(layers.slice(qkv, axes=[0], starts=[1], ends=[2]), [0])
    v = layers.squeeze(layers.slice(qkv, axes=[0], starts=[2], ends=[3]), [0])
    if use_flash:
        ctx = layers.flash_attention(q, k, v, bias=attn_bias)
    else:
        scores = layers.matmul(q, k, transpose_y=True,
                               alpha=1.0 / math.sqrt(head_dim))  # [B,Hd,S,S]
        if attn_bias is not None:
            bias4d = layers.unsqueeze(layers.unsqueeze(attn_bias, [1]), [1])
            scores = layers.elementwise_add(scores, bias4d)
        probs = layers.softmax(scores)
        if dropout and not is_test:
            probs = layers.dropout(probs, dropout, is_test=is_test,
                                   dropout_implementation="upscale_in_train")
        ctx = layers.matmul(probs, v)  # [B,Hd,S,D]
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    ctx = layers.reshape(ctx, [0, seq_len, hidden])
    return layers.fc(ctx, size=hidden, num_flatten_dims=2)


def _ffn(x, hidden, intermediate):
    h = layers.fc(x, size=intermediate, num_flatten_dims=2, act="gelu")
    return layers.fc(h, size=hidden, num_flatten_dims=2)


def bert_encoder(input_ids, token_type_ids=None, attn_mask=None,
                 vocab_size=30522, hidden=768, num_layers=12, num_heads=12,
                 seq_len=128, intermediate=3072, max_position=512,
                 type_vocab=2, dropout=0.1, is_test=False, use_flash=True):
    """Returns final hidden states [-1, S, H].

    input_ids/token_type_ids: [-1, S] int64; attn_mask: [-1, S] float32
    (1 = attend, 0 = pad) or None.
    """
    word_emb = layers.embedding(input_ids, size=[vocab_size, hidden])
    pos_ids = layers.range(0, seq_len, 1, dtype="int64")
    pos_emb = layers.embedding(pos_ids, size=[max_position, hidden])
    emb = layers.elementwise_add(word_emb, pos_emb, axis=-1)
    if token_type_ids is not None:
        type_emb = layers.embedding(token_type_ids, size=[type_vocab, hidden])
        emb = layers.elementwise_add(emb, type_emb)
    x = layers.layer_norm(emb, begin_norm_axis=2)
    if dropout and not is_test:
        x = layers.dropout(x, dropout, is_test=is_test,
                           dropout_implementation="upscale_in_train")

    attn_bias = None
    if attn_mask is not None:
        # [B,S] additive bias rows (flash path broadcasts over heads/q;
        # unfused path unsqueezes to [B,1,1,S])
        attn_bias = layers.scale(attn_mask, scale=10000.0, bias=-10000.0)

    for _ in range(num_layers):
        attn = _attention(x, hidden, num_heads, seq_len, attn_bias,
                          dropout, is_test, use_flash=use_flash)
        if dropout and not is_test:
            attn = layers.dropout(attn, dropout, is_test=is_test,
                                  dropout_implementation="upscale_in_train")
        x = layers.layer_norm(layers.elementwise_add(x, attn),
                              begin_norm_axis=2)
        ffn = _ffn(x, hidden, intermediate)
        if dropout and not is_test:
            ffn = layers.dropout(ffn, dropout, is_test=is_test,
                                 dropout_implementation="upscale_in_train")
        x = layers.layer_norm(layers.elementwise_add(x, ffn),
                              begin_norm_axis=2)
    return x


def build_bert_pretrain(batch_size=None, seq_len=128, vocab_size=30522,
                        hidden=768, num_layers=12, num_heads=12,
                        intermediate=3072, dropout=0.1, is_test=False,
                        use_flash=True, max_predictions=None):
    """MLM pretraining graph.

    Two head formulations:

    * ``max_predictions=None``: score every position over the full vocab
      ([B,S,V] logits), mask the loss.  Feeds: input_ids, token_type_ids,
      attn_mask, mlm_mask, mlm_labels — all [B,S].
    * ``max_predictions=P``: the standard pretraining data format
      (reference ERNIE/BERT create_pretraining_data): gather the P masked
      positions per sample and run the vocab projection only on them —
      head matmul and the [*,V] logits shrink by S/P (~6.7x at S=128,
      P=20), the dominant non-encoder cost.  Extra feeds: mlm_positions
      [B,P] int64, mlm_labels [B,P], mlm_weights [B,P] (0 pads unused
      slots).  Requires a fixed batch_size (the gather index builds
      a [B,P,2] coordinate tensor).

    Returns (feed_names, {'loss': ...}).
    """
    b = -1 if batch_size is None else batch_size
    input_ids = layers.data("input_ids", [b, seq_len], dtype="int64",
                            append_batch_size=False)
    token_type_ids = layers.data("token_type_ids", [b, seq_len],
                                 dtype="int64", append_batch_size=False)
    attn_mask = layers.data("attn_mask", [b, seq_len], dtype="float32",
                            append_batch_size=False)

    enc = bert_encoder(input_ids, token_type_ids, attn_mask,
                       vocab_size=vocab_size, hidden=hidden,
                       num_layers=num_layers, num_heads=num_heads,
                       seq_len=seq_len, intermediate=intermediate,
                       max_position=max(512, seq_len),
                       dropout=dropout, is_test=is_test,
                       use_flash=use_flash)

    if max_predictions is not None:
        if batch_size is None:
            raise ValueError("masked-gather head needs a fixed batch_size")
        P = int(max_predictions)
        positions = layers.data("mlm_positions", [b, P], dtype="int64",
                                append_batch_size=False)
        mlm_labels = layers.data("mlm_labels", [b, P], dtype="int64",
                                 append_batch_size=False)
        weights = layers.data("mlm_weights", [b, P], dtype="float32",
                              append_batch_size=False)
        # [B,P,2] coordinates (batch row, seq position) for gather_nd
        rows = layers.range(0, b, 1, dtype="int64")          # [B]
        rows = layers.expand(layers.unsqueeze(rows, [1]), [1, P])
        coords = layers.stack([rows, positions], axis=2)     # [B,P,2]
        picked = layers.gather_nd(enc, coords)               # [B,P,H]
        h = layers.fc(picked, size=hidden, num_flatten_dims=2, act="gelu")
        h = layers.layer_norm(h, begin_norm_axis=2)
        logits = layers.fc(h, size=vocab_size, num_flatten_dims=2)
        loss = layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(mlm_labels, [2]))       # [B,P,1]
        loss = layers.elementwise_mul(layers.squeeze(loss, [2]), weights)
        denom = layers.elementwise_add(
            layers.reduce_sum(weights),
            layers.fill_constant([1], "float32", 1e-5))
        mean_loss = layers.elementwise_div(layers.reduce_sum(loss), denom)
        feeds = ["input_ids", "token_type_ids", "attn_mask",
                 "mlm_positions", "mlm_labels", "mlm_weights"]
        return feeds, {"loss": mean_loss}

    mlm_mask = layers.data("mlm_mask", [b, seq_len], dtype="float32",
                           append_batch_size=False)
    mlm_labels = layers.data("mlm_labels", [b, seq_len], dtype="int64",
                             append_batch_size=False)
    # MLM head: transform + layernorm + vocab projection
    h = layers.fc(enc, size=hidden, num_flatten_dims=2, act="gelu")
    h = layers.layer_norm(h, begin_norm_axis=2)
    logits = layers.fc(h, size=vocab_size, num_flatten_dims=2)  # [B,S,V]
    labels = layers.unsqueeze(mlm_labels, [2])
    loss = layers.softmax_with_cross_entropy(logits, labels)  # [B,S,1]
    loss = layers.squeeze(loss, [2])
    masked = layers.elementwise_mul(loss, mlm_mask)
    denom = layers.elementwise_add(
        layers.reduce_sum(mlm_mask),
        layers.fill_constant([1], "float32", 1e-5))
    mean_loss = layers.elementwise_div(layers.reduce_sum(masked), denom)
    feeds = ["input_ids", "token_type_ids", "attn_mask", "mlm_mask",
             "mlm_labels"]
    return feeds, {"loss": mean_loss}


# bf16 activation stream: embeddings, layer norm, residual adds, softmax
# and the attention ops join AMP's matmul white list.  Master weights stay
# float32; the step is HBM-bound, so halving activation bytes is the lever.
_BF16_STREAM_OPS = ("lookup_table", "lookup_table_v2", "layer_norm",
                    "elementwise_add", "elementwise_mul", "dropout",
                    "gelu", "relu", "scale", "transpose2",
                    "reshape2", "gather_nd", "squeeze2", "unsqueeze2",
                    "flash_attention", "flash_attention_qkv", "softmax")


def build_bert_train_programs(cfg, *, learning_rate=None):
    """The flagship training recipe as (main, startup, feed_names, loss,
    bf16_stream): ``build_bert_pretrain(**cfg)`` under bf16 AMP with the
    activation-stream white list, Adam + global-norm clip at 1.0.
    ``bf16_stream`` is always True.  ``learning_rate=None`` is the
    recipe's 10 000-step linear warm-up to 1e-4; ``chip_smoke.py`` passes
    a constant, because ten steps into that warm-up nothing moves."""
    from .. import clip, optimizer, telemetry
    from ..contrib import mixed_precision
    from ..framework.core import Program, program_guard

    main_p, startup = Program(), Program()
    startup._is_startup = True
    # (forward, backward and optimizer: a part of the start-up account)
    with telemetry.startup_span("startup/program_build", kind="bert_train",
                                seq=cfg.get("seq_len")), \
            program_guard(main_p, startup):
        feed_names, outs = build_bert_pretrain(**cfg)
        lr = learning_rate
        if lr is None:
            lr = layers.linear_lr_warmup(1e-4, warmup_steps=10000,
                                         start_lr=0.0, end_lr=1e-4)
        opt = optimizer.AdamOptimizer(
            learning_rate=lr,
            grad_clip=clip.GradientClipByGlobalNorm(1.0))
        opt = mixed_precision.decorate(
            opt, dtype="bfloat16",
            amp_lists=mixed_precision.AutoMixedPrecisionLists(
                custom_white_list=_BF16_STREAM_OPS))
        opt.minimize(outs["loss"])
    return main_p, startup, feed_names, outs["loss"], True


def bert_train_flops_per_sample(seq, vocab, hidden, layers_n, inter,
                                n_pred):
    """Analytic matmul FLOPs for one BERT MLM training sample.

    Per token, per layer: QKV proj 6H^2, attn scores+PV 4*H*S, out proj
    2H^2, FFN 4*H*I (each matmul = 2mk per output elem). MLM head runs on
    the n_pred gathered positions only: (2H^2 + 2*H*V) per prediction.
    Train = 3x forward (bwd ~ 2x fwd matmul FLOPs).
    """
    per_layer = 6 * hidden ** 2 + 2 * hidden ** 2 + 4 * hidden * seq \
        + 4 * hidden * inter
    head = 2 * hidden ** 2 + 2 * hidden * vocab
    fwd = layers_n * per_layer * seq + head * n_pred
    return 3.0 * fwd
