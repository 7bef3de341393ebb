"""Plain reference for ``mistral-7b-v0.1``: the published forward pass in
float32 ``jax.numpy`` at "highest" matmul precision, with no cache, no
paging, no batching and no kernel.  Written from the model's description
(Jiang et al. 2023; the Hugging Face ``MistralForCausalLM`` equations):
pre-RMSNorm, grouped-query attention with rotary embeddings in the
rotate-half convention, causal softmax, SwiGLU, an untied head.

Departures, each noted in the configuration file: contexts are held to
the sliding window (4096), so the window mask is the causal mask.

It shares no code with the program.  ``params_from_scope`` only says under
which names the program keeps the same matrices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Roundings that a control puts in (``benchmark/tests/standins.py``): the
# plain reference leaves ``ROUND`` None, every ``_at`` is then the identity
# and adds nothing to the lowered module.  ``where`` is "residual" (the
# stream after a sublayer), "pages" (K and V as a program writes them to
# its pages) or "product" (an activation that enters a product).
ROUND = None


def _at(where, x):
    return x if ROUND is None else ROUND(where, x)


def params_from_scope(scope, cfg: dict, name: str = "llama") -> dict:
    """The program's weights, by the names ``models/llama.py`` gives them.
    The program fuses Q, K, V into one matrix and gate, up into another;
    the reference takes them apart."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    inter = cfg["intermediate_size"]

    def get(n):
        return jnp.asarray(scope.find_var(f"{name}.{n}"), jnp.float32)

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        qkv = get(f"blk{i}.qkv.w")
        gate_up = get(f"blk{i}.gate_up.w")
        layers.append({
            "ln1": get(f"blk{i}.ln1"),
            "wq": qkv[:, :heads * d],
            "wk": qkv[:, heads * d:(heads + kv) * d],
            "wv": qkv[:, (heads + kv) * d:],
            "wo": get(f"blk{i}.attn_out.w"),
            "ln2": get(f"blk{i}.ln2"),
            "w_gate": gate_up[:, :inter],
            "w_up": gate_up[:, inter:],
            "w_down": get(f"blk{i}.ffn_out.w"),
        })
    return {"embed": get("embed"), "layers": layers, "ln_f": get("ln_f"),
            "head": get("head.w")}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [heads, n, d].  Rotate-half: pairs (x[i], x[i + d/2])."""
    n, d = x.shape[1], x.shape[2]
    inv_freq = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = jnp.asarray(np.outer(np.arange(n), inv_freq), jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def forward(params: dict, token_ids, cfg: dict, rows=None):
    """Logits ``[len(rows) or n, vocab]`` of one sequence, every position
    attending to itself and all earlier ones."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // heads
    # the epsilon the program runs at, where it cannot take the published
    # one (the configuration's ``as_run`` says why)
    eps = cfg.get("as_run", {}).get("rms_norm_eps", cfg["rms_norm_eps"])
    theta = cfg["rope_theta"]
    ids = jnp.asarray(token_ids, jnp.int32)
    n = ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = _at("residual", params["embed"][ids])
        mask = jnp.tril(jnp.ones((n, n), bool))
        for p in params["layers"]:
            h = _at("product", _rms_norm(x, p["ln1"], eps))
            q = (h @ p["wq"]).reshape(n, heads, d).transpose(1, 0, 2)
            k = (h @ p["wk"]).reshape(n, kv, d).transpose(1, 0, 2)
            v = (h @ p["wv"]).reshape(n, kv, d).transpose(1, 0, 2)
            q, k = _at("product", _rope(q, theta)), _rope(k, theta)
            k, v = _at("pages", k), _at("pages", v)
            # query head g reads KV head g // (heads // kv)
            k = jnp.repeat(k, heads // kv, axis=0)
            v = jnp.repeat(v, heads // kv, axis=0)
            s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(d)
            s = jnp.where(mask[None], s, -jnp.inf)
            a = jnp.einsum("hqk,hkd->hqd",
                           _at("product", jax.nn.softmax(s, -1)), v)
            a = _at("product", a.transpose(1, 0, 2).reshape(n, heads * d))
            x = _at("residual", x + a @ p["wo"])
            h = _at("product", _rms_norm(x, p["ln2"], eps))
            x = _at("residual", x + _at(
                "product", jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"]))
                @ p["w_down"])
        x = _at("product", _rms_norm(x, params["ln_f"], eps))
        if rows is not None:
            x = x[jnp.asarray(rows)]
        return x @ params["head"]
