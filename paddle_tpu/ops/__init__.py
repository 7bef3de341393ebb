"""Operator library: importing this package registers all op lowerings.

TPU-native equivalent of the reference's operator library
(paddle/fluid/operators/ — see SURVEY.md §2.3); ops here are JAX lowering
rules compiled by XLA instead of per-op CUDA kernels.
"""
from . import math_ops  # noqa: F401
from . import tensor_ops  # noqa: F401
from . import nn_ops  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import collective_ops  # noqa: F401
from . import amp_ops  # noqa: F401
from . import control_flow_ops  # noqa: F401
from . import dgc_ops  # noqa: F401
from . import attention_ops  # noqa: F401
from . import rope_ops  # noqa: F401
from . import decode_ops  # noqa: F401
from . import gated_delta_ops  # noqa: F401
from . import ssd_ops  # noqa: F401
from . import latent_attention_ops  # noqa: F401
from . import io_ops  # noqa: F401
from . import debug_ops  # noqa: F401
from . import sequence_ops  # noqa: F401
from . import quant_ops  # noqa: F401
from . import detection_ops  # noqa: F401
from . import beam_ops  # noqa: F401
from . import crf_ctc_ops  # noqa: F401
from . import misc_ops  # noqa: F401
from . import nn_extra_ops  # noqa: F401
from . import linalg_ops  # noqa: F401
from . import interp_extra_ops  # noqa: F401
from . import pool_extra_ops  # noqa: F401
from . import misc2_ops  # noqa: F401
from . import rnn_fused_ops  # noqa: F401
from . import catalog_seq_ops  # noqa: F401
from . import catalog_ctr_ops  # noqa: F401
from . import moe_ops  # noqa: F401
from .registry import (LowerContext, all_registered_ops, get_op_def,  # noqa
                       has_op, register_op)
