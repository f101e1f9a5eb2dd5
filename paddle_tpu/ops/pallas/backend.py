"""Where this package's kernels run, and the MXU layout helpers they share.

Every gate of a kernel asks `on_kernels()` for its backend half: the TPU,
or a test that pinned the kernels (`FORCE_PALLAS`), where they run
interpreted. Kernel modules read these names as attributes of this module
at call time (`backend.interpret()`), never through `from ... import`, so
that one patch of `interpret` or `FORCE_PALLAS` reaches every kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# test hook: run the kernels off the TPU, interpreted
FORCE_PALLAS = False


def interpret() -> bool:
    """Off the TPU (the CPU tests) the kernels run in interpreter mode for
    exact-semantics checking."""
    return jax.default_backend() != "tpu"


def on_kernels() -> bool:
    """Whether the kernels run where a gate admits the shapes: on the TPU,
    or where a test pins them (interpreted)."""
    return FORCE_PALLAS or not interpret()


def identity(n):
    """[n, n] f32 identity for MXU-side layout transposes (built once per
    grid cell, outside the head loop)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return (r == c).astype(jnp.float32)


def to_lanes(x_sparse, ident):
    """(n, 1) sublane-major -> (1, n) lane-major via an MXU matmul.

    The VPU relayout Mosaic emits for a plain reshape walks 1-lane-wide
    vregs and costs ~0.7us per call (profiled: it was 40% of the whole
    fwd kernel); the [1,n]x[n,n] identity matmul is noise on the MXU."""
    return jax.lax.dot_general(
        x_sparse, ident, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def to_sublanes(x_lane, ident):
    """(1, n) lane-major -> (n, 1) sublane-major via an MXU matmul."""
    return jax.lax.dot_general(
        ident, x_lane, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
