#!/usr/bin/env python
"""What one dropout site's mask bits cost on the chip, by how they are drawn.

    chiprun -- python tools/dropout_microbench.py

Times, at BERT-base's site shapes (``[64, 512, 768]`` on one chip,
``[40, 512, 768]`` a ``dp`` shard, bf16 input, p = 0.1), the draw, the
compare against the uint8 threshold and the select:

* ``threefry_u8`` -- ``jax.random.bits`` on a threefry key (what the op ran
  until PR 33);
* ``rbg_u8`` -- ``lax.rng_bit_generator`` drawing bytes (what
  ``ops/nn_ops.py`` ``_draw_mask_bits`` runs);
* ``rbg_u32_flat`` / ``_last`` / ``_lead`` -- a quarter as many ``u32``
  words bitcast to bytes, drawn flat, along the last dim, along the first.

Each as the mask alone, as one site's select, and as five chained sites in
one program, beside one elementwise pass over the input.  Writes
``chiprun_out/dropout_bits_bench.json`` and prints one line per form.
Refuses to run without a TPU backend (``--rehearse``: a toy shape on
whatever backend there is, to check the forms).
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

T = 26  # round(0.1 * 256)


def words(key):
    return jnp.resize(jax.random.key_data(key).ravel(), 4)


def bits_threefry(key, shape):
    return jax.random.bits(key, shape, "uint8")


def bits_u8(key, shape):
    return lax.rng_bit_generator(words(key), shape, dtype="uint8")[1]


def bits_u32_flat(key, shape):
    n = int(np.prod(shape))
    w = lax.rng_bit_generator(words(key), (n // 4,), dtype="uint32")[1]
    return lax.bitcast_convert_type(w, jnp.uint8).reshape(shape)


def bits_u32_last(key, shape):
    w = lax.rng_bit_generator(words(key), shape[:-1] + (shape[-1] // 4,),
                              dtype="uint32")[1]
    return lax.bitcast_convert_type(w, jnp.uint8).reshape(shape)


def bits_u32_lead(key, shape):
    # four bytes of a word to four slices of the leading dim
    w = lax.rng_bit_generator(words(key), (shape[0] // 4,) + shape[1:],
                              dtype="uint32")[1]
    b = lax.bitcast_convert_type(w, jnp.uint8)          # [..., 4]
    return jnp.moveaxis(b, -1, 0).reshape(shape)


FORMS = {"threefry_u8": bits_threefry, "rbg_u8": bits_u8,
         "rbg_u32_flat": bits_u32_flat, "rbg_u32_last": bits_u32_last,
         "rbg_u32_lead": bits_u32_lead}


def timed(fn, *args, n=30):
    for _ in range(2):  # compile, then one warm call
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main(shapes, n):
    res = {"device": jax.devices()[0].device_kind, "rows": []}
    key = jax.random.fold_in(jax.random.key(np.uint32(7)), 3)
    for shape in shapes:
        x = jnp.ones(shape, jnp.bfloat16)
        base = timed(jax.jit(lambda x: (x * 1.5).astype(x.dtype)), x, n=n)
        for name, form in FORMS.items():
            mask = jax.jit(lambda k, f=form: f(k, shape) >= jnp.uint8(T))
            sel = jax.jit(lambda k, x, f=form: jnp.where(
                f(k, shape) >= jnp.uint8(T), x * 1.5, 0).astype(x.dtype))

            # 5 sites in one program, each with its key
            def five(k, x, f=form):
                for i in range(5):
                    x = jnp.where(f(jax.random.fold_in(k, i), shape)
                                  >= jnp.uint8(T), x * 1.5, 0).astype(x.dtype)
                return x
            row = {"shape": list(shape), "form": name,
                   "mask_only_ms": timed(mask, key, n=n),
                   "select_ms": timed(sel, key, x, n=n),
                   "five_sites_ms": timed(jax.jit(five), key, x, n=n),
                   "elementwise_pass_ms": base,
                   "keep_rate": float(jnp.mean(
                       mask(key).astype(jnp.float32)))}
            print(json.dumps(row), flush=True)
            res["rows"].append(row)
    return res


if __name__ == "__main__":
    if "--rehearse" in sys.argv:
        main(((8, 64, 128),), 2)
    else:
        assert jax.default_backend() == "tpu", jax.default_backend()
        res = main(((64, 512, 768), (40, 512, 768)), 30)
        os.makedirs("chiprun_out", exist_ok=True)
        json.dump(res, open("chiprun_out/dropout_bits_bench.json", "w"),
                  indent=1)
