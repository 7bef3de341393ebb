"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of PaddlePaddle Fluid (~1.8/2.0-beta), built on jax/XLA/pallas/pjit.

Architecture (vs. the reference, see SURVEY.md):
  * Program/Block/Operator IR mirrors fluid's ProgramDesc, but execution
    lowers whole blocks to single XLA computations (no op interpreter).
  * Collectives are sharding annotations + XLA collectives over ICI,
    not NCCL ops.
  * The imperative mode shares the same op lowerings via an eager tracer.
"""
# importing the package is the first part of the start-up account
# (``startup/import``, recorded on the last line; telemetry.py)
import sys as _sys
import time as _time
_import_t0 = _time.monotonic()

# the lock-order sanitizer must patch threading BEFORE any module
# constructs its locks, so this hook runs first (no-op unless
# FLAGS_debug_lock_order is set in the environment)
from . import locksan as _locksan  # noqa: E402
_locksan.install_from_flag()

# the operator library imports jax; import it here, where it can be timed
# apart from the package's own modules (0 when it was imported before)
_jax_t0 = None if "jax" in _sys.modules else _time.monotonic()
import jax as _jax  # noqa: E402,F401
_jax_ms = 0.0 if _jax_t0 is None else (_time.monotonic() - _jax_t0) * 1e3

from . import ops  # registers the operator library
from .framework.core import (Program, Variable, Parameter, OpRole,  # noqa
                             default_main_program, default_startup_program,
                             program_guard, unique_name, in_dygraph_mode,
                             convert_dtype, grad_var_name, device_guard)
from .framework.executor import (AsyncRunResult, Executor,  # noqa
                                 FetchHandle, Scope, global_scope,
                                 scope_guard)
from .framework.backward import append_backward, gradients  # noqa
from .framework.layer_helper import ParamAttr, WeightNormParamAttr  # noqa
from .framework import initializer  # noqa
from .framework import ir  # noqa
from . import layers  # noqa
from . import optimizer  # noqa
from . import regularizer  # noqa
from . import clip  # noqa
from .layers.tensor import data  # noqa
from . import dygraph  # noqa
from .dygraph import jit  # noqa  (paddle.jit 2.0 namespace)
from .framework.compiler import (CompiledProgram, BuildStrategy,  # noqa
                                 ExecutionStrategy, ParallelExecutor)
from . import distributed  # noqa
from . import contrib  # noqa
from . import io  # noqa
from . import checkpoint  # noqa
from . import reader  # noqa
from .reader import DataLoader, DataFeeder, batch  # noqa
from . import inference  # noqa
from . import serving  # noqa  (dynamic-batching inference engine + HTTP)
from . import profiler  # noqa
from .flags import get_flags, set_flags  # noqa
from . import fault  # noqa  (deterministic fault injection)
from .train_guard import TrainGuard, TrainingInterrupted  # noqa
from . import memory  # noqa
from . import tensor  # noqa  (paddle.tensor 2.0 namespace)
from . import monitor  # noqa  (StatRegistry + graphviz dumps)
from . import telemetry  # noqa  (spans, typed metrics, exporters)
from . import amp  # noqa  (paddle.amp 2.0 namespace)
from . import errors  # noqa
from .errors import EnforceNotMet, enforce  # noqa
from . import vision  # noqa
from . import text  # noqa
from . import metrics  # noqa
from . import dataset  # noqa
from .dataset import DatasetFactory  # noqa
from . import transpiler  # noqa
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa
from . import metric  # noqa
from . import nn  # noqa
from . import static  # noqa
from . import hapi  # noqa
from .hapi import Model  # noqa

__version__ = "0.1.0"


# -- device places (API parity; jax owns actual placement) -------------------
class CPUPlace:
    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    """The TPU device place — the reference's CUDAPlace analog."""

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


CUDAPlace = TPUPlace  # scripts written for the reference keep working


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    import jax
    return any(d.platform == "tpu" for d in jax.devices())


def device_count() -> int:
    import jax
    return jax.device_count()


# fluid-compat namespace: `import paddle_tpu.fluid as fluid`
from . import fluid  # noqa  (must come after the symbols above exist)

telemetry.span_record(
    "startup/import", _import_t0, _time.monotonic(),
    jax_ms=round(_jax_ms, 3),
    modules=sum(m.startswith("paddle_tpu.") for m in list(_sys.modules)))
