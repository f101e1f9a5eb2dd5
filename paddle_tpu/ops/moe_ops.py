"""Mixture-of-Experts feed-forward ops: `moe_ffn` and `moe_swiglu`.

Two ops, two formulations, and which model takes which:

- `moe_ffn` is the capacity-bounded GShard / Switch layer: softmax router,
  every expert a static `capacity` of slots, dispatch and combine as dense
  `[T, E, C]` one-hot einsums, two-matrix experts with biases, tokens over
  capacity get zero, and a load-balancing auxiliary loss. It holds all E
  experts; sharding the expert axis over an "ep" mesh axis
  (`fleet.apply_expert_parallel`) lets GSPMD insert the all-to-all pair.
  `BertConfig.moe_num_experts` builds it; nothing about it changed when
  `moe_swiglu` arrived.
- `moe_swiglu` is the dropless layer of today's open sparse decoders:
  sigmoid scores, a selection bias that picks the top-k but does not weigh
  them, renormalised gates, three-matrix SwiGLU experts without biases. It
  is **told which experts it holds** (`first_expert`, and `E_held` from the
  weights' leading axis) while its router keeps the published width: it
  sorts the (token, pick) pairs that fall on its own experts by expert,
  runs one grouped product a projection over those groups
  (`ops/pallas/grouped_matmul.py`, below) and returns the held
  experts' part of the layer's result. Nothing is dropped at any
  imbalance, there is no `[T, E, C]` tensor and no capacity; on one chip
  there is no exchange and nothing stands in for the absent chips.
  `models/lfm2_moe.py` builds it. Built without W3 its experts are the
  two-matrix squared-ReLU ones of the Nemotron line, W2 relu(W1 x)^2
  (`models/nemotron_h.py`), through the same route, dispatch, sorted
  block and combine: two grouped products a block and not three.

  **Who multiplies.** The three products of a block and their transposes
  (input and weight gradients) go through `grouped_matmul`, one
  `jax.custom_vjp` a product. On the TPU, for bf16 or float32 rows, at
  matrices whose two extents are multiples of 64 (a multiple of 128 is
  tiled, 1856 is one block of its whole extent) and a row count a row tile
  divides, each of the three forms runs a Pallas kernel written for the
  chip (`moe_gmm_nn`, `moe_gmm_nt`, `moe_gmm_tn`; tiles from the operands'
  shapes alone). On every other backend, and at every other shape, the
  CPU tests and the benchmark's `--rehearse` included, each form is
  `jax.lax.ragged_dot` or its transpose as autodiff writes it:
  `ragged_dot` stays in the tree as the kernels' `jnp` composition, as
  what XLA lowers to a grouped-matmul kernel of its own on the TPU where
  the gate refuses, and as two of the three forms of the dropless
  fallback below (`_FULL_WIDTH_KERNELS` says which and why). At the LFM2
  cell's operands XLA's kernel took 1.27-1.60 ms a call and the Pallas
  ones 0.80-0.91 (PERF.md, PR 31). Both follow the rows present, not the
  buffer, and neither reads nor writes a row behind the last group.

  **The sorted block's rows.** The products follow the rows present;
  every gather, mask and element-wise pass beside them follows the buffer.
  So the block is not T * k rows, the worst imbalance, but
  `sorted_rows`: twice what uniform routing sends this share
  (`2 * T * k * E_held / E`, up to a multiple of 512), read from the
  op's own shapes, no attribute and no flag. A share of a quarter of the
  router works on half the rows, a share of an eighth on a quarter; a
  share of half or all of it has T * k rows and lowers as it always did.
  Where the bound is below T * k the block is lowered twice, at the
  bound and at T * k under the scope `moe_full_width`, and a device
  scalar (do the held experts' pairs fit?) picks one each step through
  `jax.lax.cond`, once in the forward and once in the backward pass: no
  host sync, no recompilation, nothing dropped. A step that falls back
  costs what a T * k-row step costs; the second lowering costs every
  process its tracing and, in a cold cache, its compile. The combine's
  transpose is written by hand (`_combine`): in sorted order it reads
  d_out by token, one gather of the block's rows, where autodiff wrote a
  [T, k, H] cotangent out and gathered it back.

`moe_ffn`, in detail: a fused top-k router + capacity-bounded dispatch +
per-expert FFN, expressed entirely as dense einsums over a one-hot dispatch
tensor. That formulation is the TPU-idiomatic one for a capacity-bounded
layer: every FLOP-carrying contraction is a large static-shape
einsum the MXU can tile, and when the expert dimension of W1/W2 is sharded
over an "ep" mesh axis (fleet.apply_expert_parallel) while tokens are
sharded over "dp", XLA's SPMD partitioner inserts the all-to-all pair
around the expert computation automatically — no hand-written dispatch
collective, mirroring how the rest of this framework gets its collectives
from GSPMD rather than a transpiler pass.

Exposed through the same surfaces as every other capability:
  fluid.layers.moe_ffn(...)            (layer DSL)
  fluid.layers.moe_swiglu(...)
  DistributedStrategy.expert_parallel  (fleet strategy -> "ep" axis)

`moe_ffn` semantics:
  X      [B, S, H]   tokens
  GateW  [H, E]      router weights
  W1     [E, H, F]   expert up-projection
  B1     [E, F]
  W2     [E, F, H]   expert down-projection
  B2     [E, H]
  ->
  Out     [B, S, H]  combined expert outputs (tokens over capacity get 0
                     from the expert path; callers keep the residual)
  AuxLoss []         Switch load-balancing loss, E * sum_e f_e * P_e
                     (1.0 when perfectly balanced)

Routing runs in float32 regardless of compute dtype (softmax/cumsum are
balance-critical); the expert einsums run in the input dtype so AMP
applies to the FLOP-heavy path only.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .registry import register


def moe_capacity(num_tokens: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    """Static per-expert capacity: ceil(top_k * T / E * factor)."""
    return max(1, int(math.ceil(top_k * num_tokens / num_experts * capacity_factor)))


def _activation(name: str):
    return {
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "silu": jax.nn.silu,
        "swish": jax.nn.silu,
        "tanh": jnp.tanh,
    }[name]


@register("moe_ffn")
def moe_ffn(ctx, ins, attrs):
    x = ins["X"][0]
    gate_w = ins["GateW"][0]
    w1, b1 = ins["W1"][0], ins["B1"][0]
    w2, b2 = ins["W2"][0], ins["B2"][0]

    top_k = int(attrs.get("top_k", 2))
    capacity_factor = float(attrs.get("capacity_factor", 1.25))
    act = _activation(str(attrs.get("activation", "gelu")))

    b, s, h = x.shape
    e = w1.shape[0]
    t = b * s
    cap = moe_capacity(t, e, top_k, capacity_factor)

    x2 = x.reshape(t, h)

    # ---- router (float32) ------------------------------------------------
    logits = jnp.einsum(
        "th,he->te", x2.astype(jnp.float32), gate_w.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]

    # top-k selection, slot by slot; later slots see earlier picks masked
    remaining = probs
    slot_idx, slot_gate = [], []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)  # [T]
        oh = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        slot_idx.append(oh)
        slot_gate.append(jnp.sum(remaining * oh, axis=-1))  # [T]
        remaining = remaining * (1.0 - oh)
    # top-1 (Switch) keeps the RAW router prob as the gate — normalizing
    # would make it identically 1.0 and sever the task-loss gradient into
    # GateW; top-k>1 normalizes selected gates to sum to 1 (GShard combine),
    # which preserves the gradient through the relative weighting
    if top_k > 1:
        denom = sum(slot_gate)
        slot_gate = [g / jnp.maximum(denom, 1e-9) for g in slot_gate]

    # ---- capacity-bounded dispatch/combine tensors -----------------------
    # slot 0 claims positions first; slot 1 queues behind it (GShard order)
    counts = jnp.zeros((e,), jnp.float32)
    dispatch = jnp.zeros((t, e, cap), jnp.float32)
    combine = jnp.zeros((t, e, cap), jnp.float32)
    for oh, gate in zip(slot_idx, slot_gate):
        pos = jnp.cumsum(oh, axis=0) - oh + counts[None, :]  # [T, E]
        keep = oh * (pos < cap)  # [T, E]
        pos_oh = jax.nn.one_hot(jnp.sum(pos * oh, axis=-1).astype(jnp.int32),
                                cap, dtype=jnp.float32)  # [T, C]
        d = keep[:, :, None] * pos_oh[:, None, :]  # [T, E, C]
        dispatch = dispatch + d
        combine = combine + d * gate[:, None, None]
        counts = counts + jnp.sum(oh, axis=0)

    # ---- expert computation (input dtype: the AMP-able FLOPs) ------------
    disp = dispatch.astype(x.dtype)
    expert_in = jnp.einsum("tec,th->ech", disp, x2)  # [E, C, H]
    h1 = jnp.einsum("ech,ehf->ecf", expert_in, w1) + b1[:, None, :]
    h1 = act(h1)
    eout = jnp.einsum("ecf,efh->ech", h1, w2) + b2[:, None, :]
    out2 = jnp.einsum("tec,ech->th", combine.astype(x.dtype), eout)

    # ---- Switch load-balancing auxiliary loss ----------------------------
    # f_e: fraction of tokens whose FIRST choice is e; P_e: mean router prob
    frac = jnp.mean(slot_idx[0], axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)

    return {"Out": [out2.reshape(b, s, h)], "AuxLoss": [aux.astype(jnp.float32)]}


# ---------------------------------------------------------------------------
# moe_swiglu: dropless, bias-routed, a held share of the experts
# ---------------------------------------------------------------------------


def route_sigmoid_topk(x2, gate_w, expert_bias, top_k, norm_topk_prob,
                       routed_scaling_factor):
    """Scores, selection and gates, all float32: s = sigmoid(x W_g) over
    the router's whole width; the top-k of s + bias are the picks (the
    bias selects, it does not weigh and takes no gradient); the gates are
    the picks' own s, renormalised over the k picks where asked.
    Returns (picks [T, k] int32, gates [T, k] float32)."""
    logits = jnp.dot(x2.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    biased = s + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
    _, picks = jax.lax.top_k(biased, top_k)
    gates = jnp.take_along_axis(s, picks, axis=-1)
    if norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return picks.astype(jnp.int32), gates * routed_scaling_factor


def expert_load(picks, num_experts: int):
    """Picks each expert of the router received, [E] int32."""
    return jnp.sum(
        picks.reshape(-1)[:, None] == jnp.arange(num_experts)[None, :],
        axis=0, dtype=jnp.int32)


def balance_bias(expert_bias, load, rate: float):
    """Auxiliary-loss-free balancing (Wang et al. 2024, arXiv:2408.15664,
    the rule DeepSeek-V3 trains with): after a step's picks, every
    expert's selection bias moves by `rate` towards the mean load,
    b_e += rate * sign(mean load - load_e), over the router's whole width
    and the tokens at hand. The bias is what the next step selects by; it
    never weighs an output and takes no gradient. rate 0: unchanged."""
    bias = jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
    if rate == 0.0:
        return bias
    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


# The sorted block of a held share has room for this many times the rows
# uniform routing sends it. 2: under the balancing rule every sparse trainer
# runs (`balance_bias`) a share stays within a few per cent of its
# expectation (1.00-1.06 x a layer in the LFM2 cell, from random weights),
# and a router left to the loss alone drifts by tens of per cent; twice the
# expectation holds both, and still halves the rows of a share of a quarter.
# Beyond it nothing is dropped: the block is then T * k rows (`_held_part`).
_ROW_HEADROOM = 2
_ROW_MULTIPLE = 512  # the bound is rounded up to whole blocks of rows


def sorted_rows(pairs: int, held: int, experts: int) -> int:
    """Static rows of the sorted block of a layer that holds `held` of a
    router's `experts`, over `pairs` = T * k (token, pick) pairs."""
    expected = -(-_ROW_HEADROOM * pairs * held // experts)
    return min(pairs, -(-expected // _ROW_MULTIPLE) * _ROW_MULTIPLE)


@jax.custom_vjp
def _gather_rows(src, index, back_index, back_valid):
    """src[index] whose transpose is a gather too: the caller knows the
    inverse map, d_src[r] = sum over the last axis of
    d_out[back_index[r, :]] where `back_valid`, so the backward pass makes
    no scatter-add (serial on the TPU) out of a permutation."""
    return jnp.take(src, index, axis=0, mode="clip")


def _gather_rows_fwd(*operands):
    return _gather_rows(*operands), operands[2:]


def _gather_rows_bwd(saved, d_out):
    back_index, back_valid = saved
    rows = jnp.take(d_out, back_index, axis=0, mode="clip")
    rows = jnp.where(back_valid[..., None], rows.astype(jnp.float32), 0.0)
    return jnp.sum(rows, axis=-2).astype(d_out.dtype), None, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@jax.custom_vjp
def _combine(ys, gates, slot, placed, head, present):
    """out[t] = sum over the placed picks of gates[t, j] * ys[slot[t, j]],
    float32 sums, in `ys`' dtype. Its transpose works in sorted order, on
    the rows that exist: d_ys[r] = gate of pair head[r] x d_out[its token],
    one gather of len(head) rows out of the T-row d_out, where autodiff
    would write the [T, k, H] cotangent out and gather it back by `head`."""
    picked = jnp.take(ys, slot, axis=0, mode="clip")  # [T, k, H]
    weighted = jnp.where(placed[..., None], picked.astype(jnp.float32), 0.0)
    return jnp.sum(weighted * gates[..., None], axis=1).astype(ys.dtype)


def _combine_fwd(*operands):
    return _combine(*operands), operands


def _combine_bwd(saved, d_out):
    ys, gates, slot, placed, head, present = saved
    top_k = gates.shape[1]
    d_row = jnp.take(d_out, head // top_k, axis=0,
                     mode="clip").astype(jnp.float32)
    d_row = jnp.where(present[:, None], d_row, 0.0)  # [rows, H]
    gate_row = jnp.take(gates.reshape(-1), head, axis=0, mode="clip")
    d_ys = (d_row * gate_row[:, None]).astype(ys.dtype)
    d_gate_row = jnp.sum(d_row * ys.astype(jnp.float32), axis=-1)
    d_gates = jnp.where(placed, jnp.take(d_gate_row, slot, mode="clip"), 0.0)
    return d_ys, d_gates.astype(gates.dtype), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped_experts(xs, weights, group_sizes, kernels=None):
    """The grouped products and the activation: rows of `xs` lie sorted by
    expert, `group_sizes[e]` of them for expert e, and what lies behind
    the last group is computed by nobody. `weights` is (W1, W3, W2) for
    SwiGLU experts, W2 (silu(W1 x) * W3 x), and (W1, W2) for squared-ReLU
    ones, W2 relu(W1 x)^2. `grouped_matmul` is the Pallas kernel where its
    gate serves the operands (the TPU, the matrices' extents in multiples
    of 64) and `jax.lax.ragged_dot` elsewhere, forward and transposed alike;
    `kernels` names the forms that may take the kernel (None: all three)."""
    # imported here: a program without this op never loads Pallas
    from .pallas.grouped_matmul import FORMS, grouped_matmul

    product = functools.partial(grouped_matmul, kernels=kernels or FORMS)
    a = product(xs, weights[0].astype(xs.dtype), group_sizes)
    if len(weights) == 3:
        g = product(xs, weights[1].astype(xs.dtype), group_sizes)
        inter = (jax.nn.silu(a.astype(jnp.float32))
                 * g.astype(jnp.float32)).astype(xs.dtype)
    else:
        inter = jnp.square(jax.nn.relu(a.astype(jnp.float32))
                           ).astype(xs.dtype)
    return product(inter, weights[-1].astype(xs.dtype), group_sizes)


def _sorted_block(rows, x2, gates, weights, order, slot, mine,
                  group_sizes, kernels=None):
    """The held experts' part of the layer out of a sorted block of `rows`
    rows, which has to hold every pair on a held expert: gather the pairs'
    tokens in sorted order, the grouped products of `_grouped_experts`
    over `weights`, weigh and sum back by token. `order` sorts the T * k
    pairs by held expert (the others behind), `slot` [T, k] is its inverse,
    `mine` [T, k] the pairs on held experts. A pair whose slot lies behind
    `rows` is on nobody's expert here and is masked as `mine` masks.
    `kernels`: `_grouped_experts`'."""
    top_k = gates.shape[1]
    with jax.named_scope("moe_dispatch"):
        head = order[:rows]
        placed = mine & (slot < rows)
        # a row behind the last group belongs to nobody: the grouped
        # products neither read nor write it, so it is zero going in and
        # masked coming out
        present = jnp.arange(rows) < jnp.sum(group_sizes)
        xs = jnp.where(present[:, None],
                       _gather_rows(x2, head // top_k, slot, placed), 0)
    with jax.named_scope("moe_experts"):
        ys = _grouped_experts(xs, weights, group_sizes, kernels)
    with jax.named_scope("moe_combine"):
        return _combine(ys, gates, slot, placed, head, present)


# The forms of the grouped product that run the Pallas kernel in the
# dropless fallback. A form lowered at a second row count is two more
# kernel bodies for every process to trace and lower (~0.1 s each on the
# benchmark's host, in the step and again in the check program), for a
# block that runs only when routing has left the rails; the product and
# the weight gradient therefore keep XLA's `ragged_dot` there (it follows
# the rows present too). The input gradient keeps the kernel: XLA's own
# wants the weights in a second layout beside the one the bounded block's
# kernels read, and the step's peak, which the fallback's T * k-row
# buffers set, grows by 0.29 GB (PERF.md, PR 31).
_FULL_WIDTH_KERNELS = ("nt",)


def _full_width(x2, gates, *operands):
    """The dropless fallback: the same block at T * k rows."""
    with jax.named_scope("moe_full_width"):
        return _sorted_block(gates.size, x2, gates, *operands,
                             kernels=_FULL_WIDTH_KERNELS)


def _fits(rows, group_sizes):
    return jnp.sum(group_sizes) <= rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_part(rows, x2, gates, weights, order, slot, mine, group_sizes):
    """`_sorted_block` at `rows` rows where the held experts' pairs fit,
    else at T * k: a conditional on a device scalar, no host sync and
    nothing dropped. The conditional stands outside differentiation: under
    `jax.vjp` a `cond` hands back the union of its branches' residuals,
    zero-filled for the branch not taken, so the bounded branch would write
    the other's T * k-row buffers. Here the forward keeps its operands
    alone and the backward is a second conditional whose branches each run
    and transpose their own block."""
    return jax.lax.cond(_fits(rows, group_sizes),
                        functools.partial(_sorted_block, rows), _full_width,
                        x2, gates, weights, order, slot, mine, group_sizes)


def _held_part_fwd(rows, *operands):
    return _held_part(rows, *operands), operands


def _held_part_bwd(rows, operands, d_out):
    def transposed(block, *operands):
        trained, indices = operands[:3], operands[3:]
        return jax.vjp(lambda *t: block(*t, *indices), *trained)[1](d_out)

    d_trained = jax.lax.cond(
        _fits(rows, operands[-1]),
        functools.partial(transposed, functools.partial(_sorted_block, rows)),
        functools.partial(transposed, _full_width), *operands)
    return (*d_trained, None, None, None, None)


_held_part.defvjp(_held_part_fwd, _held_part_bwd)


@register("moe_swiglu")
def moe_swiglu(ctx, ins, attrs):
    """X [B, S, H], GateW [H, E], ExpertBias [E], W1 / W3 [E_held, H, F],
    W2 [E_held, F, H] -> Out [B, S, H], the part of the layer's result that
    experts first_expert .. first_expert + E_held - 1 give (without W3 the
    experts are the two-matrix squared-ReLU ones, W2 relu(W1 x)^2: the
    route, the dispatch, the sorted block and the combine are the same),
    TokensPerExpert [E_held] int32, the rows each of them received, and
    ExpertBiasOut [E], the selection bias after the balancing rule
    (`balance_bias`), which a training program binds to ExpertBias itself.

    The expert products are `ops/pallas/grouped_matmul.py`'s: Pallas
    kernels on the TPU at shapes their gate serves, `jax.lax.ragged_dot`
    everywhere else (the module docstring says why both stay).

    The sorted block has `sorted_rows(T * k, E_held, E)` rows. A layer that
    holds half of its router or more has T * k of them and lowers without a
    conditional; `remat` then decides whether the block's buffers are kept
    for the backward pass (False) or the block is run again there (True).
    A smaller share lowers the block twice, bounded and, under the scope
    `moe_full_width`, at T * k rows, and a device scalar picks one each
    step, forward and backward; such a layer always keeps its operands
    alone and runs the block again in the backward pass, whatever `remat`
    says (buffers kept across a conditional would be both branches')."""
    x = ins["X"][0]
    gate_w, expert_bias = ins["GateW"][0], ins["ExpertBias"][0]
    w1 = ins["W1"][0]
    weights = ((w1, ins["W3"][0], ins["W2"][0]) if "W3" in ins
               else (w1, ins["W2"][0]))
    top_k = int(attrs.get("top_k", 4))
    first = int(attrs.get("first_expert", 0))
    held = w1.shape[0]
    if not 0 <= first <= gate_w.shape[1] - held:
        raise ValueError(
            f"moe_swiglu: experts {first}..{first + held - 1} of a router "
            f"that is {gate_w.shape[1]} wide")
    b, s, h = x.shape
    t = b * s
    x2 = x.reshape(t, h)

    with jax.named_scope("moe_route"):
        picks, gates = route_sigmoid_topk(
            x2, gate_w, expert_bias, top_k,
            bool(attrs.get("norm_topk_prob", True)),
            float(attrs.get("routed_scaling_factor", 1.0)))
        load = expert_load(picks, gate_w.shape[1])
        new_bias = balance_bias(expert_bias, load,
                                float(attrs.get("bias_update_rate", 0.0)))

    with jax.named_scope("moe_dispatch"):
        # (token, pick) pairs sorted by held expert; pairs that fall on
        # experts held elsewhere sort behind the last group
        local = picks - first
        mine = (local >= 0) & (local < held)  # [T, k]
        key = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        slot = jnp.argsort(order).astype(jnp.int32).reshape(t, top_k)
        group_sizes = load[first:first + held]

    rows = sorted_rows(t * top_k, held, gate_w.shape[1])
    if rows < t * top_k:
        held_part = functools.partial(_held_part, rows)
    else:
        held_part = functools.partial(_sorted_block, rows)
        # remat keeps the tokens and the gates for the backward pass and
        # gathers and multiplies again there: the [T*k, .] row buffers of
        # a layer are then alive in one layer at a time
        if attrs.get("remat", False):
            held_part = jax.checkpoint(held_part)
    out = held_part(x2, gates, weights, order, slot, mine, group_sizes)

    return {"Out": [out.reshape(b, s, h)], "TokensPerExpert": [group_sizes],
            "ExpertBiasOut": [new_bias]}
