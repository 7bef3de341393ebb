"""Plain reference for ``bert-base-mlm``: BERT's forward pass and masked
language-model loss in float32 ``jax.numpy`` at "highest" matmul
precision, dropout off, no kernel and no mixed precision.  Written from
the paper (Devlin et al. 2018) and the Hugging Face ``BertForMaskedLM``
equations: learned word, position and segment embeddings, post-LayerNorm
encoder layers with GELU, and the MLM head (dense + GELU + LayerNorm +
vocabulary projection) on the masked positions.

Departure, noted in the configuration file: the decoder matrix is not
tied to the word embedding (the program's ``build_bert_pretrain`` keeps a
separate one), so the reference takes it as its own parameter.

It shares no code with the program.  ``params_from_program`` only says in
which order the program created the same matrices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def params_from_program(program, scope, cfg: dict) -> dict:
    """The program's parameters in creation order: three embeddings and
    their LayerNorm; per layer QKV, output, LayerNorm, FFN in, FFN out,
    LayerNorm; then the head's transform, LayerNorm and decoder."""
    vals = [jnp.asarray(scope.find_var(p.name), jnp.float32)
            for p in program.all_parameters()]
    it = iter(vals)

    def take(k):
        return [next(it) for _ in range(k)]

    word, pos, typ, ln_w, ln_b = take(5)
    layers = []
    for _ in range(cfg["num_hidden_layers"]):
        (wqkv, bqkv, wo, bo, ln1w, ln1b,
         w1, b1, w2, b2, ln2w, ln2b) = take(12)
        layers.append(dict(wqkv=wqkv, bqkv=bqkv, wo=wo, bo=bo, ln1w=ln1w,
                           ln1b=ln1b, w1=w1, b1=b1, w2=w2, b2=b2,
                           ln2w=ln2w, ln2b=ln2b))
    wt, bt, lnhw, lnhb, wd, bd = take(6)
    if next(it, None) is not None:
        raise ValueError("the program has parameters the reference "
                         "does not know")
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    if word.shape != (v, h) or wd.shape != (h, v) \
            or layers[0]["wqkv"].shape != (h, 3 * h):
        raise ValueError("parameter order is not the one expected")
    return dict(word=word, pos=pos, typ=typ, ln_w=ln_w, ln_b=ln_b,
                layers=layers, wt=wt, bt=bt, lnhw=lnhw, lnhb=lnhb,
                wd=wd, bd=bd)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


def mlm_logits_and_loss(params: dict, batch: dict, cfg: dict):
    """``(logits [B, P, V], loss)`` for a batch in the program's feed
    format (``input_ids``, ``token_type_ids``, ``attn_mask``,
    ``mlm_positions``, ``mlm_labels``, ``mlm_weights``)."""
    heads = cfg["num_attention_heads"]
    # the epsilon the program runs at, where it cannot take the published
    # one (the configuration's ``as_run`` says why)
    eps = cfg.get("as_run", {}).get("layer_norm_eps", cfg["layer_norm_eps"])
    ids = jnp.asarray(batch["input_ids"], jnp.int32)
    b, s = ids.shape
    hdim = cfg["hidden_size"]
    d = hdim // heads
    with jax.default_matmul_precision("highest"):
        x = params["word"][ids] + params["pos"][:s][None] \
            + params["typ"][jnp.asarray(batch["token_type_ids"], jnp.int32)]
        x = _layer_norm(x, params["ln_w"], params["ln_b"], eps)
        bias = (1.0 - jnp.asarray(batch["attn_mask"], jnp.float32)) * -1e4
        for p in params["layers"]:
            qkv = x @ p["wqkv"] + p["bqkv"]
            q, k, v = (qkv[..., i * hdim:(i + 1) * hdim]
                       .reshape(b, s, heads, d).transpose(0, 2, 1, 3)
                       for i in range(3))
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
            sc = sc + bias[:, None, None, :]
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)
            a = a.transpose(0, 2, 1, 3).reshape(b, s, hdim)
            x = _layer_norm(x + a @ p["wo"] + p["bo"],
                            p["ln1w"], p["ln1b"], eps)
            f = _gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
            x = _layer_norm(x + f, p["ln2w"], p["ln2b"], eps)
        pos = jnp.asarray(batch["mlm_positions"], jnp.int32)
        picked = jnp.take_along_axis(x, pos[..., None], axis=1)
        t = _gelu(picked @ params["wt"] + params["bt"])
        t = _layer_norm(t, params["lnhw"], params["lnhb"], eps)
        logits = t @ params["wd"] + params["bd"]
        logp = jax.nn.log_softmax(logits, -1)
        labels = jnp.asarray(batch["mlm_labels"], jnp.int32)
        nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        w = jnp.asarray(batch["mlm_weights"], jnp.float32)
        loss = jnp.sum(nll * w) / (jnp.sum(w) + 1e-5)
    return logits, loss
