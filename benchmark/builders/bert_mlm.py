"""Builder for configurations of the BERT MLM family: published keys ->
the program's ``bench.build_bert_train_programs``, host batches of its
feed format, the reference check and the FLOPs a token needs.  A
configuration names it under ``"builder"``; ``train.py`` calls
``build``, ``host_batches``, ``reference_check`` and ``flops_per_token``
and knows nothing else of the family."""
from __future__ import annotations

import numpy as np

import ops_bytes
from traffic import _rng


def max_predictions(cfg: dict, seq: int) -> int:
    return max(1, int(round(cfg["recipe"]["masked_share"] * seq)))


def build(cfg: dict, batch: int, seq: int, dropout: float):
    """``(main, startup, feed_names, loss)`` of the training program."""
    import bench
    from paddle_tpu.framework.core import reset_unique_name

    # both builds must name their parameters alike to share one scope
    reset_unique_name()
    main_p, startup, feed_names, loss, _ = bench.build_bert_train_programs(
        dict(batch_size=batch, seq_len=seq,
             vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
             num_layers=cfg["num_hidden_layers"],
             num_heads=cfg["num_attention_heads"],
             intermediate=cfg["intermediate_size"],
             max_predictions=max_predictions(cfg, seq), use_flash=True,
             dropout=dropout))
    return main_p, startup, feed_names, loss


def host_batches(seed: int, cfg: dict, batch: int, seq: int,
                 n_distinct: int) -> list:
    """Host batches of the BERT MLM format (a copy of
    ``bench._make_host_batches`` with the seed as an argument): uniform
    token ids, every position attended, ``max_predictions`` distinct
    masked positions per sequence, sorted."""
    vocab, max_pred = cfg["vocab_size"], max_predictions(cfg, seq)
    rng = _rng(seed, 4)
    out = []
    for _ in range(n_distinct):
        pos = np.sort(rng.permuted(
            np.tile(np.arange(seq), (batch, 1)), axis=1)[:, :max_pred],
            axis=1).astype("int64")
        out.append({
            "input_ids": rng.integers(0, vocab, (batch, seq)).astype("int64"),
            "token_type_ids": np.zeros((batch, seq), "int64"),
            "attn_mask": np.ones((batch, seq), "float32"),
            "mlm_positions": pos,
            "mlm_labels": rng.integers(
                0, vocab, (batch, max_pred)).astype("int64"),
            "mlm_weights": np.ones((batch, max_pred), "float32"),
        })
    return out


def flops_per_token(cfg: dict, seq: int) -> float:
    return ops_bytes.bert_train_flops_per_token(
        cfg, seq, max_predictions(cfg, seq))


def reference_check(run, cfg, scope, seq, seed) -> bool:
    """The program's forward pass (bf16 AMP, Pallas attention, dropout
    off) on two sequences against the plain float32 reference on the same
    weights: MLM logits within the configuration's tolerance of the
    reference's range, and the loss."""
    import paddle_tpu as pt

    tol = run.cell.tolerance
    main_p, _, _, loss = build(cfg, 2, seq, 0.0)
    fwd = main_p.clone(for_test=True)
    logits_name = next(op for op in fwd.global_block().ops
                       if op.type == "softmax_with_cross_entropy"
                       ).input("Logits")[0]
    batch = host_batches(seed, cfg, 2, seq, 1)[0]
    place = pt.CPUPlace() if run.rehearse else pt.TPUPlace()
    got_loss, got_logits = pt.Executor(place).run(
        fwd, feed=batch, fetch_list=[loss.name, logits_name], scope=scope)
    ref = run.cell.reference()
    params = ref.params_from_program(main_p, scope, cfg)
    want_logits, want_loss = ref.mlm_logits_and_loss(params, batch, cfg)
    got_logits = np.asarray(got_logits, "float32")
    want_logits = np.asarray(want_logits)
    rel = float(np.abs(got_logits - want_logits).max()
                / np.abs(want_logits).max())
    got_loss = float(np.asarray(got_loss).reshape(-1)[0])
    want_loss = float(want_loss)
    rel_loss = abs(got_loss - want_loss) / abs(want_loss)
    ok = bool(np.isfinite(got_logits).all() and rel <= tol
              and rel_loss <= tol)
    run.check = {"tolerance": tol, "rel_logits": rel, "rel_loss": rel_loss,
                 "finite": bool(np.isfinite(got_logits).all())}
    run.say(f"reference check: MLM logits {got_logits.shape} off the "
            f"float32 reference by {rel:.4g} of its range, loss "
            f"{got_loss:.5f} against {want_loss:.5f} ({rel_loss:.3g}); "
            f"tolerance {tol:.4g}: {'ok' if ok else 'FAILED'}")
    return ok
