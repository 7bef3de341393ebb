"""Is a training cell's step reproducible to the bit, and where do two trees
part?  (chip only; "rehearse" runs a two-layer cut on the CPU mesh)

    python tools/step_bits.py <tree> <cell> <seed> <steps> <tag> [grads] [rehearse]

runs the cell's training step as benchmark/train.py builds it, from the
checkout <tree> (this one, or a `git archive` of another commit unpacked
under an ignored directory), and writes chiprun_out/det_<tag>.json: every
loss whole (float.hex) and, after every step, a wrapping uint32 sum of the
bits of every array of the step's state (parameters, Adam moments,
counters), computed on the device.  With "grads": before the run, one step
of a second build of the same program that also fetches the first and the
last layer's QKV@GRAD, from a copy of the initial state: their bit sums
and sha256.  Compare two files by hand: equal losses and sums are the same
run; the first (step, array) that differs says where two trees part.
PR 35 ran the parent twice with it (bert-base-seq512-dp4, 30 steps: equal
to the bit); see PERF.md section 6."""
import hashlib, itertools, json, os, sys
tree, cell_name, seed, steps, tag = (os.path.abspath(sys.argv[1]), sys.argv[2], int(sys.argv[3]),
                                     int(sys.argv[4]), sys.argv[5])
with_grads, dry = "grads" in sys.argv[6:], "rehearse" in sys.argv[6:]
out = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "chiprun_out", f"det_{tag}.json")
os.makedirs(os.path.dirname(out), exist_ok=True)
os.chdir(tree)
sys.path[:0] = [os.path.join(tree, "benchmark"), tree]
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import harness
import paddle_tpu as pt
from paddle_tpu.parallel import build_sharded_step, dp_mesh

cell = harness.Cell(cell_name)
cfg, mix, builder = cell.cfg, cell.mix, cell.builder()
n = cell.chips
devices = jax.devices()
assert dry or devices[0].platform == "tpu", devices
assert len(devices) >= n, devices
if dry:
    mix = dict(mix, **mix["rehearse"])
    cfg = dict(cfg, num_hidden_layers=2, vocab_size=1000)
seq, batch = int(mix["seq_len"]), int(mix["per_chip_batch"]) * n
main_p, startup, feed_names, loss = builder.build(cfg, batch, seq, cfg["recipe"]["dropout"])
scope = pt.Scope()
pt.Executor(pt.CPUPlace() if dry else pt.TPUPlace()).run(startup, scope=scope)
harness.seeded_weights(scope, [p.name for p in main_p.all_parameters()], seed)
mesh = dp_mesh(n, devices=devices[:n])
fn, mut_in, const_in, _ = build_sharded_step(main_p, feed_names, [loss.name], mesh)
host = builder.host_batches(seed, cfg, batch, seq, int(mix["distinct_batches"]))
stream = (tuple(b[k] for k in feed_names) for b in itertools.cycle(host))
mut = tuple(scope.find_var(k) for k in mut_in)
const = tuple(scope.find_var(k) for k in const_in)
sh = NamedSharding(mesh, P("dp"))


def bits(x):
    if x.dtype.itemsize == 4:
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    elif x.dtype.itemsize == 2:
        u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    else:
        u = x.astype(jnp.uint32)
    return jnp.sum(u.ravel(), dtype=jnp.uint32)


checksum = jax.jit(lambda xs: [bits(x) for x in xs])
result = {"cell": cell_name, "seed": seed, "tree": tree, "state": list(mut_in)}

if with_grads:
    qkv = [op.output("QKV@GRAD")[0] for op in main_p.global_block().ops
           if op.type == "flash_attention_qkv_grad"]
    wanted = [qkv[-1], qkv[0]]          # grad ops run last layer first
    fn_g, mut_g, const_g, _ = build_sharded_step(main_p, feed_names, [loss.name] + wanted, mesh)
    assert list(mut_g) == list(mut_in) and list(const_g) == list(const_in)
    first = tuple(jax.device_put(host[0][k], sh) for k in feed_names)
    copy = tuple(jnp.copy(x) if isinstance(x, jax.Array) else x for x in mut)
    fetches, _, _ = fn_g(first, copy, const, np.int32(1))
    result["grads"] = {}
    for name, g in zip(["layer0", "layer_last"], fetches[1:]):
        a = np.asarray(g)
        result["grads"][name] = {
            "var": wanted[["layer0", "layer_last"].index(name)], "shape": list(a.shape), "dtype": str(a.dtype),
            "bits_sum": int(np.asarray(checksum([g])[0])),
            "sha256": hashlib.sha256(a.tobytes()).hexdigest(),
            "abs_mean": float(np.abs(a.astype(np.float32)).mean())}
    result["grads"]["loss"] = float(np.asarray(fetches[0]).reshape(-1)[0]).hex()
    del fetches, copy

losses, sums = [], []
for step in range(1, steps + 1):
    feed = tuple(jax.device_put(x, sh) for x in next(stream))
    fetches, mut, _ = fn(feed, mut, const, np.int32(step))
    losses.append(float(np.asarray(fetches[0]).reshape(-1)[0]))
    sums.append([int(v) for v in np.asarray(checksum(list(mut)))])
result.update(losses=[x.hex() for x in losses], as_float=losses, sums=sums)
json.dump(result, open(out, "w"))
print(tag, losses[0], losses[-1], result.get("grads", {}).get("layer0", {}).get("sha256", "")[:16])
