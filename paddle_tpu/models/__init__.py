"""Model zoo covering the tracked benchmark configs (BASELINE.json):

* MNIST LeNet        — models.lenet          (static, single device)
* ResNet-50 ImageNet — models.resnet         (data-parallel)
* BERT/ERNIE-base    — models.bert           (Fleet collective)
* Llama-style LLM    — models.llama          (DP + recompute + tp/sp)
* Wide&Deep CTR      — planned (parameter-server sparse path)

All are built with the paddle_tpu static-graph layers API (the reference
keeps its equivalents in separate repos — PaddleClas/PaddleNLP — plus the
in-tree book tests python/paddle/fluid/tests/book/).
"""
from .lenet import lenet, build_mnist_train  # noqa
from .resnet import resnet, build_resnet_train  # noqa
from .bert import (bert_encoder, build_bert_pretrain,  # noqa
                   build_bert_train_programs,
                   bert_train_flops_per_sample)
from .llama import (llama, llama_block, build_llama_train,  # noqa
                    build_llama_forward, build_llama_prefill,
                    build_llama_decode)
from .seq2seq import build_seq2seq_train, build_seq2seq_infer  # noqa
