#!/usr/bin/env python
"""Hash the routed expert layer as a tree lowers it for the chip.

    python tools/moe_layer_hash.py TREE [--plain 1]

Lowers ``TREE``'s ``parallel/moe.py`` ``moe_routed_tokens`` for a described
TPU v5e (no chip: ``jax.default_backend`` is answered with "tpu", shapes
alone are lowered, nothing is compiled or run) at a decode step's and a
prefill rung's rows of the three configurations that hold one chip's share
of an expert-parallel group (``held_first``), with ``valid`` fed, and prints
a hash of each StableHLO text and its count of Mosaic calls.  ``--plain 1``
adds the three configurations without ``held_first``, with and without
``valid``.

Two trees build the same programs where the hashes agree.  A Mosaic call's
serialised body holds its call sites' file and line, so compare two trees
AT ONE PATH (a symlink moved from one to the other does) and expect a
difference wherever a call site moved: PR 52 and PR 54 kept ``parallel/
moe.py``'s lines where their parents had them for this comparison.
"""
import hashlib
import os
import sys

# name: (router's experts, held, top k, K, width I, step's rows, a rung's)
HELD = {"solar-open2-250b": (320, 20, 8, 4096, 1280, 64, 4096),
        "gigachat35-432b-a28b": (256, 8, 8, 7168, 2048, 32, 2048),
        "command-a-plus-05-2026": (128, 8, 8, 4096, 4096, 10, 1024)}
# name: (experts, top k, K, width I, gate, step's or pass's rows, a rung's)
PLAIN = {"smallthinker-21b-a3b": (64, 6, 2560, 768, "relu", 32, 8192),
         "lfm2-24b-a2b": (64, 4, 2048, 1536, "silu", 64, 4096),
         "sdar-30b-a3b-chat": (128, 8, 2048, 768, "silu", 192, 1024)}


def main(argv) -> int:
    tree = os.path.abspath(argv[1])
    sys.path.insert(0, tree)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"
    from paddle_tpu.parallel import moe

    if not moe.__file__.startswith(tree + os.sep):
        print(f"{moe.__file__} is not under {tree}", file=sys.stderr)
        return 2
    highest = jax.lax.Precision.HIGHEST

    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def said(what, layer, n, K, E, groups, I, valid=True):
        text = jax.jit(layer).lower(
            spec(n, K), spec(K, E), spec(groups, K, 2 * I),
            spec(groups, I, K),
            spec(n, dtype=jnp.bool_) if valid else None).as_text()
        print(what, f"{n} rows", hashlib.sha256(text.encode()).hexdigest()[
            :16], text.count("tpu_custom_call"), "Mosaic calls", flush=True)

    for name, (E, held, k, K, I, step, rung) in HELD.items():
        for n in (step, rung):
            said(f"{name} held", lambda x, r, gu, dn, v, k=k:
                 moe.moe_routed_tokens(
                     x, x, r, gu, dn, top_k=k, activation="silu", valid=v,
                     precision=highest, score="sigmoid", held_first=0),
                 n, K, E, held, I)
    if "--plain" in argv:
        for name, (E, k, K, I, gate, step, rung) in PLAIN.items():
            for n in (step, rung):
                for valid in (True, False):
                    said(f"{name} valid={'fed' if valid else None}",
                         lambda x, r, gu, dn, v, k=k, gate=gate:
                         moe.moe_routed_tokens(
                             x, x, r, gu, dn, top_k=k, activation=gate,
                             valid=v, precision=highest),
                         n, K, E, E, I, valid)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
