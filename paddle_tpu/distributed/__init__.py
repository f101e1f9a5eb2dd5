"""paddle.distributed-style collective API.

Parity surface: /root/reference/python/paddle/distributed/ (launch.py,
collective wrappers fluid/layers/collective.py:20-172) and the c_* op
family (operators/collective/).

TPU-native design: a "process group" is a named mesh axis. Collectives are
the jax.lax primitives over that axis; they run inside a manual-SPMD region
(`shard_map` over the mesh), which is how the reference's per-rank SPMD
program view maps onto single-controller JAX. Two usage levels:

1. In-shard functions (all_reduce, all_gather, ...): call inside a
   shard_map body — the direct analog of calling c_allreduce_sum inside a
   per-rank program.
2. `collective(fn, mesh, in_specs, out_specs)`: wrap a per-rank function
   over global arrays (builds the shard_map), the analog of running a
   transpiled per-rank program under the launcher.

Multi-host bootstrap (reference launch.py + gen_nccl_id) is
`init_parallel_env()` → jax.distributed.initialize.
"""
from __future__ import annotations

from typing import Optional, Sequence

from ..parallel.env import get_rank, get_world_size, init_parallel_env  # noqa: F401
from ..parallel import create_mesh  # noqa: F401
from ..parallel.ring_attention import ring_attention, ring_attention_global  # noqa: F401


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


def all_reduce(tensor, op: str = ReduceOp.SUM, group: str = "dp"):
    """Reduce across the `group` mesh axis (in-shard; reference
    c_allreduce_{sum,max,min,prod}_op)."""
    import jax.numpy as jnp
    from jax import lax

    if op == ReduceOp.SUM:
        return lax.psum(tensor, group)
    if op == ReduceOp.MAX:
        return lax.pmax(tensor, group)
    if op == ReduceOp.MIN:
        return lax.pmin(tensor, group)
    if op == ReduceOp.PROD:
        # no lax.pprod primitive: gather then reduce (exp(psum(log)) would
        # NaN on negatives and lose precision)
        return jnp.prod(lax.all_gather(tensor, group, axis=0), axis=0)
    raise ValueError(f"unknown reduce op {op!r}")


def all_gather(tensor, group: str = "dp", axis: int = 0):
    """Concatenate every participant's tensor along `axis` (reference
    c_allgather_op)."""
    from jax import lax

    return lax.all_gather(tensor, group, axis=axis, tiled=True)


def reduce_scatter(tensor, group: str = "dp", axis: int = 0):
    """Sum across participants, scatter blocks of `axis` (reference
    c_reducescatter_op)."""
    from jax import lax

    return lax.psum_scatter(tensor, group, scatter_dimension=axis, tiled=True)


def broadcast(tensor, src: int = 0, group: str = "dp"):
    """Every participant gets rank `src`'s tensor (reference c_broadcast_op)."""
    import jax.numpy as jnp
    from jax import lax

    idx = lax.axis_index(group)
    return lax.psum(jnp.where(idx == src, tensor, jnp.zeros_like(tensor)), group)


def reduce(tensor, dst: int = 0, op: str = ReduceOp.SUM, group: str = "dp"):
    """Reduce to rank `dst`; other ranks get zeros (reference c_reduce_op)."""
    import jax.numpy as jnp
    from jax import lax

    total = all_reduce(tensor, op, group)
    idx = lax.axis_index(group)
    return jnp.where(idx == dst, total, jnp.zeros_like(total))


def scatter(tensor, src: int = 0, group: str = "dp", axis: int = 0):
    """Rank `src`'s tensor is split along `axis`; rank i gets block i
    (reference c_scatter_op)."""
    import jax.numpy as jnp
    from jax import lax

    full = broadcast(tensor, src, group)
    n = lax.psum(1, group)
    idx = lax.axis_index(group)
    if full.shape[axis] % n != 0:
        raise ValueError(
            f"scatter: dim {axis} of size {full.shape[axis]} is not "
            f"divisible by the group size {n}"
        )
    block = full.shape[axis] // n
    return lax.dynamic_slice_in_dim(full, idx * block, block, axis)


def send_recv(tensor, perm: Sequence, group: str = "dp"):
    """Point-to-point ring exchange: perm is [(src, dst), ...] pairs
    (lax.ppermute; the analog of the reference's send/recv ops on ICI)."""
    from jax import lax

    return lax.ppermute(tensor, group, list(perm))


def barrier(group: str = "dp"):
    """Reference barrier op: under single-program XLA the whole step is one
    synchronized computation, so this is a no-op kept for API parity."""
    return None


def collective(fn, mesh, in_specs, out_specs, check_vma: bool = False):
    """Run per-rank `fn` over global arrays on `mesh` (shard_map wrapper)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def get_group(axis: str = "dp"):
    """Parity helper: a 'group' is just the mesh axis name."""
    return axis
