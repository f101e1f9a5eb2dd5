"""paddle_tpu.fluid — static-graph front end.

Parity surface: python/paddle/fluid/__init__.py in the reference. The same
Program/Executor/layers/optimizer API, executing through whole-block XLA JIT.
"""
from . import (  # noqa: F401
    backward,
    clip,
    dtypes,
    dygraph,
    framework,
    initializer,
    io,
    layers,
    optimizer,
    param_attr,
    regularizer,
    unique_name,
)
from . import checkpoint, compiler, crypto, dataset, learning_rate_scheduler, metrics, monitor, nets, profiler, reader, transpiler  # noqa: F401
from .checkpoint import CheckpointManager  # noqa: F401
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from .compiler import BuildStrategy, CompiledProgram, ExecutionStrategy  # noqa: F401
from .flags import get_flags, set_flags  # noqa: F401
from .dataset import DatasetFactory  # noqa: F401
from .reader import DataLoader, PyReader  # noqa: F401
from .backward import append_backward, calc_gradient, gradients  # noqa: F401

# reference exposes schedules under fluid.layers.* too
for _n in (
    "noam_decay",
    "exponential_decay",
    "natural_exp_decay",
    "inverse_time_decay",
    "polynomial_decay",
    "piecewise_decay",
    "cosine_decay",
    "linear_lr_warmup",
):
    setattr(layers, _n, getattr(learning_rate_scheduler, _n))
del _n
from .executor import Executor, Scope, global_scope, scope_guard  # noqa: F401
from .framework import (  # noqa: F401
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    in_dygraph_mode,
    name_scope,
    program_guard,
)
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401


class CPUPlace:
    """Place tags kept for API parity; JAX/PJRT owns actual placement."""

    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


# the reference's CUDAPlace maps to a TPU chip here
CUDAPlace = TPUPlace
XLAPlace = TPUPlace


class CUDAPinnedPlace:
    def __repr__(self):
        return "CUDAPinnedPlace"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def cuda_places(device_ids=None):
    return [TPUPlace(i) for i in (device_ids or [0])]


def cpu_places(device_count=None):
    return [CPUPlace()]


def device_count() -> int:
    import jax

    return jax.device_count()


# data layer (fluid.data in 1.8+)
def data(name, shape, dtype="float32", lod_level=0):
    return layers.tensor.data(
        name, shape, dtype, lod_level, append_batch_size=False
    )


def embedding(*args, **kwargs):
    return layers.embedding(*args, **kwargs)
