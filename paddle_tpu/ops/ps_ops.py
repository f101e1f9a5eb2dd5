"""distributed_lookup_table: device <-> host-PS embedding bridge.

Parity: reference operators/distributed_ops/distributed_lookup_table
(trainer side) + the pserver optimizer block it pairs with. The forward
gathers rows from the host table via jax.pure_callback; the backward is
an io_callback that PUSHES the rows' gradients to the server, which
applies its own optimizer (ps.ShardedHostTable.push_gradients) — so the
device-side program never materializes or differentiates the table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register


@functools.lru_cache(maxsize=64)
def _lookup_fn(table_name: str, dim: int, out_dtype: str):
    from ..distributed import ps

    dt = jnp.dtype(out_dtype)

    @jax.custom_vjp
    def lookup(ids, anchor):
        # `anchor` is the (1,) float Parameter that carries the vjp to
        # this op: gradients only propagate along DIFFERENTIABLE inputs,
        # and ids are integers — without a float input on the custom_vjp
        # itself, jax.vjp would treat the lookup as a constant and the
        # backward push would never run
        flat = ids.reshape(-1)
        rows = jax.pure_callback(
            lambda i: ps.get_table(table_name).gather(i).astype(out_dtype),
            jax.ShapeDtypeStruct((flat.shape[0], dim), dt),
            flat,
        )
        return rows.reshape(ids.shape + (dim,)) + (anchor[0] * 0).astype(dt)

    def fwd(ids, anchor):
        return lookup(ids, anchor), (ids, anchor)

    def bwd(res, g):
        ids, anchor = res
        flat = ids.reshape(-1)
        gflat = g.reshape(flat.shape[0], dim)

        def push(i, gr):
            ps.get_table(table_name).push_gradients(i, gr)
            return np.int32(0)

        from jax.experimental import io_callback

        # pin the push to one device: SPMD partitioning forbids replicated
        # side-effecting custom-calls, and the server update must apply
        # exactly once per step regardless of mesh size. Unordered: within
        # a step the push is data-dependent on the gather (through the
        # loss), and cross-step reordering is the documented async-PS
        # (Downpour) semantics — ordered=True would also thread a token
        # whose replicated tuple sharding the SPMD partitioner rejects
        token = io_callback(
            push, jax.ShapeDtypeStruct((), jnp.int32), flat, gflat,
            sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]),
        )
        # anchor's gradient is identically zero; the token dependency
        # keeps the push effect anchored in the cotangent
        danchor = jnp.zeros_like(anchor) + token.astype(anchor.dtype) * 0
        return (None, danchor)

    lookup.defvjp(fwd, bwd)
    return lookup


@register("distributed_lookup_table", no_vjp_grad=False)
def distributed_lookup_table(ctx, ins, attrs):
    """Inputs: Ids [B,...] int; W — a (1,) zero anchor Parameter (the
    trainer-side stub: autodiff's needs-grad walk seeds from Parameters,
    and the host table is NOT a program Parameter by design, so the
    anchor is what makes backward reach this op; its own gradient is
    identically zero)."""
    from ..distributed import ps

    ids = ins["Ids"][0]
    name = attrs["table_names"][0] if "table_names" in attrs else attrs["table_name"]
    table = ps.get_table(name)
    fn = _lookup_fn(name, table.dim, str(np.dtype(table.dtype)))
    anchor = (
        ins["W"][0] if ins.get("W") else jnp.zeros((1,), jnp.float32)
    )
    return {"Outputs": [fn(ids, anchor)]}
