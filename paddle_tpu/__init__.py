"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle Fluid 1.8 (reference: /root/reference).

Static-graph programs (fluid.Program) are JIT-compiled whole-block via
XLA; distributed training uses jax.sharding meshes + XLA collectives over
ICI/DCN; hot kernels use Pallas. See SURVEY.md for the design blueprint.
"""
__version__ = "0.1.0"


def compile_cache_dir():
    """Where this package points JAX's persistent compilation cache, or
    None when JAX_COMPILATION_CACHE_DIR is set: JAX reads that variable
    itself and the package then names no directory at all. Otherwise one
    fixed directory beside the package, `<checkout>/.jax_cache`: the
    path is part of the cache key, so it never depends on the working
    directory, a pid, a time or a temporary name."""
    import os

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def _arm_compile_cache():
    """THE one cache site, run at import — before anything can compile.
    A chip run is a cold machine every time; the step's executables are
    stored whatever their compile time or size so that a second process
    (and aot_step after run) reads them back."""
    import os

    import jax

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # a CPU run compiles in seconds, and XLA:CPU logs a page of
        # machine-feature warnings for every executable it reads back
        return
    path = compile_cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


_arm_compile_cache()

from . import dataset, fluid, hapi, inference, io, nn, ops, reader, telemetry, tensor  # noqa: F401
from .tensor import *  # noqa: F401,F403 — 2.0 puts tensor ops at the root
from .fluid import (  # noqa: F401
    CPUPlace,
    Executor,
    ParamAttr,
    Program,
    TPUPlace,
    Variable,
    default_main_program,
    default_startup_program,
    global_scope,
    program_guard,
    scope_guard,
)

CUDAPlace = fluid.CUDAPlace
XLAPlace = fluid.XLAPlace


def batch(reader_fn, batch_size, drop_last=False):
    """Group a sample reader into a batch reader (reference
    python/paddle/batch.py)."""

    def batch_reader():
        b = []
        for sample in reader_fn():
            b.append(sample)
            if len(b) == batch_size:
                yield b
                b = []
        if b and not drop_last:
            yield b

    return batch_reader
