"""Shim: ``benchmark/builders/bert_mlm.py:21`` and
``benchmark/tests/test_benchmark.py:220`` import these two names from here; the
next ``benchmark`` PR re-points them to ``paddle_tpu.models.bert`` and deletes
this file."""
from paddle_tpu.models.bert import (build_bert_train_programs,  # noqa: F401
                                    bert_train_flops_per_sample)

__all__ = ["build_bert_train_programs", "bert_train_flops_per_sample"]
