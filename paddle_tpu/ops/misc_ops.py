"""Breadth ops: activations, selection, uniqueness, hashing, metrics.

Parity surface: reference operators/ selu_op.cc, activation_op.cc
(brelu/soft_relu/stanh), multiplex_op.cc, unique_with_counts_op.cc (+
unique_op.cc), sampling_id_op.cc, hash_op.cc, mean_iou_op.cc,
data_norm_op.cc, row_conv_op.cc, im2sequence_op.cc, shuffle_channel_op.cc,
space_to_depth_op.cc, bilinear_tensor_product_op.cc, spectral_norm_op.cc.

Static-shape notes: `unique`/`unique_with_counts` return SAME-SIZE outputs
(the unique prefix followed by padding) plus a scalar count — XLA cannot
produce data-dependent shapes; callers slice with the count host-side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..fluid.dtypes import runtime_dtype
from .registry import register


@register("selu")
def selu(ctx, ins, attrs):
    x = ins["X"][0]
    scale = float(attrs.get("scale", 1.0507009873554805))
    alpha = float(attrs.get("alpha", 1.6732632423543772))
    return {"Out": [scale * jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1.0))]}


@register("brelu")
def brelu(ctx, ins, attrs):
    x = ins["X"][0]
    t_min = float(attrs.get("t_min", 0.0))
    t_max = float(attrs.get("t_max", 24.0))
    return {"Out": [jnp.clip(x, t_min, t_max)]}


@register("soft_relu")
def soft_relu(ctx, ins, attrs):
    x = ins["X"][0]
    t = float(attrs.get("threshold", 40.0))
    return {"Out": [jnp.log1p(jnp.exp(jnp.clip(x, -t, t)))]}


@register("stanh")
def stanh(ctx, ins, attrs):
    x = ins["X"][0]
    a = float(attrs.get("scale_a", 0.67))
    b = float(attrs.get("scale_b", 1.7159))
    return {"Out": [b * jnp.tanh(a * x)]}


@register("multiplex")
def multiplex(ctx, ins, attrs):
    """Ids [B,1] selects which of the N stacked X tensors supplies row b
    (reference multiplex_op.cc)."""
    ids = ins["Ids"][0].astype(jnp.int32).reshape(-1)
    xs = jnp.stack(ins["X"], axis=0)  # [N, B, ...]
    rows = jnp.arange(xs.shape[1])
    return {"Out": [xs[ids, rows]]}


@register("unique_with_counts", stop_gradient=True, no_vjp_grad=True)
def unique_with_counts(ctx, ins, attrs):
    """1-D unique with static output sizes: Out is [N] (unique prefix;
    jnp.unique(size=..., fill_value=None) pads the tail by REPEATING THE
    SMALLEST unique value), Index [N] maps x -> position in Out, Count
    [N] (0 beyond the unique prefix — use it or UniqueCount to find the
    real prefix length), UniqueCount [] scalar."""
    x = ins["X"][0].reshape(-1)
    uniq, idx, counts = jnp.unique(
        x, return_inverse=True, return_counts=True, size=x.shape[0],
        fill_value=None,
    )
    n_unique = (counts > 0).sum()
    return {
        "Out": [uniq],
        "Index": [idx.astype(jnp.int32).reshape(-1)],
        "Count": [counts.astype(jnp.int32)],
        "UniqueCount": [n_unique.astype(jnp.int32)],
    }


@register("unique", stop_gradient=True, no_vjp_grad=True)
def unique(ctx, ins, attrs):
    r = unique_with_counts(ctx, ins, attrs)
    return {"Out": r["Out"], "Index": r["Index"], "UniqueCount": r["UniqueCount"]}


@register("sampling_id", stop_gradient=True, no_vjp_grad=True)
def sampling_id(ctx, ins, attrs):
    """Sample one class id per row from probabilities X [B, C]
    (reference sampling_id_op.cc)."""
    x = ins["X"][0]
    key = ctx.salted_rng(int(attrs.get("rng_salt", 0))) if attrs.get(
        "rng_salt") is not None else ctx.rng()
    ids = jax.random.categorical(key, jnp.log(jnp.maximum(x, 1e-30)), axis=-1)
    return {"Out": [ids.astype(runtime_dtype("int64"))]}


@register("hash", stop_gradient=True, no_vjp_grad=True)
def hash_op(ctx, ins, attrs):
    """Deterministic integer hashing of int ids into [0, mod_by) with
    num_hash independent hash functions (reference hash_op.cc uses xxhash;
    here a Knuth multiplicative mix — different values, same contract:
    deterministic, well-spread)."""
    x = ins["X"][0].astype(jnp.uint32)
    num_hash = int(attrs.get("num_hash", 1))
    mod_by = int(attrs.get("mod_by", 1))
    outs = []
    for i in range(num_hash):
        h = (x + jnp.uint32(i * 0x9E3779B9)) * jnp.uint32(2654435761)
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        outs.append((h % jnp.uint32(mod_by)).astype(runtime_dtype("int64")))
    # reference emits [rows, num_hash, 1] for [rows, 1] input
    return {"Out": [jnp.stack(outs, axis=1).reshape(x.shape[0], num_hash, -1)]}


@register("mean_iou", stop_gradient=True, no_vjp_grad=True)
def mean_iou(ctx, ins, attrs):
    """Mean intersection-over-union over classes (reference mean_iou_op.cc).
    Predictions/Labels int [*]; num_classes static."""
    pred = ins["Predictions"][0].reshape(-1).astype(jnp.int32)
    label = ins["Labels"][0].reshape(-1).astype(jnp.int32)
    nc = int(attrs["num_classes"])
    p1 = jax.nn.one_hot(pred, nc, dtype=jnp.float32)
    l1 = jax.nn.one_hot(label, nc, dtype=jnp.float32)
    inter = (p1 * l1).sum(0)
    union = p1.sum(0) + l1.sum(0) - inter
    present = union > 0
    iou = jnp.where(present, inter / jnp.maximum(union, 1.0), 0.0)
    miou = iou.sum() / jnp.maximum(present.sum(), 1)
    return {
        "OutMeanIou": [miou.astype(jnp.float32)],
        "OutWrong": [(l1.sum(0) - inter).astype(jnp.int32)],
        "OutCorrect": [inter.astype(jnp.int32)],
    }


@register("data_norm")
def data_norm(ctx, ins, attrs):
    """Normalization from accumulated batch statistics (reference
    data_norm_op.cc, CTR models): scale/shift derived from running
    size/sum/squared-sum accumulators rather than per-batch stats."""
    x = ins["X"][0]
    bsize = ins["BatchSize"][0]
    bsum = ins["BatchSum"][0]
    bsq = ins["BatchSquareSum"][0]
    eps = float(attrs.get("epsilon", 1e-4))
    means = bsum / bsize
    # reference data_norm_op.cc:302: scale = sqrt(size / square_sum) —
    # no mean^2 subtraction (the accumulators are mean-removed upstream)
    scales = jnp.sqrt(bsize / jnp.maximum(bsq, eps))
    out = (x - means) * scales
    return {"Y": [out], "Means": [means], "Scales": [scales]}


@register("row_conv")
def row_conv(ctx, ins, attrs):
    """Lookahead row convolution (reference row_conv_op.cc): X [B,T,D],
    Filter [future_context+1, D]; out[t] = sum_k f[k] * x[t+k]."""
    x, f = ins["X"][0], ins["Filter"][0]
    ctx_len = f.shape[0]
    padded = jnp.pad(x, [(0, 0), (0, ctx_len - 1), (0, 0)])
    out = sum(
        padded[:, k : k + x.shape[1]] * f[k][None, None, :]
        for k in range(ctx_len)
    )
    return {"Out": [out]}


@register("im2sequence", stop_gradient=False)
def im2sequence(ctx, ins, attrs):
    """Slide a window over [N,C,H,W] and lay patches out as a sequence
    [N, L, C*kh*kw] (reference im2sequence_op.cc; dense analog of its
    LoD output)."""
    x = ins["X"][0]
    kh, kw = [int(v) for v in attrs["kernels"]]
    sh, sw = [int(v) for v in attrs.get("strides", [1, 1])]
    pads = [int(v) for v in attrs.get("paddings", [0, 0, 0, 0])]
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), [(pads[0], pads[2]), (pads[1], pads[3])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # [N, C*kh*kw, Ho, Wo]
    n, ckk, ho, wo = patches.shape
    return {"Out": [patches.reshape(n, ckk, ho * wo).transpose(0, 2, 1)]}


@register("shuffle_channel")
def shuffle_channel(ctx, ins, attrs):
    x = ins["X"][0]
    g = int(attrs.get("group", 1))
    n, c, h, w = x.shape
    out = x.reshape(n, g, c // g, h, w).swapaxes(1, 2).reshape(n, c, h, w)
    return {"Out": [out]}


@register("space_to_depth")
def space_to_depth(ctx, ins, attrs):
    x = ins["X"][0]
    bs = int(attrs.get("blocksize", 1))
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // bs, bs, w // bs, bs)
    out = out.transpose(0, 3, 5, 1, 2, 4)
    return {"Out": [out.reshape(n, c * bs * bs, h // bs, w // bs)]}


@register("bilinear_tensor_product")
def bilinear_tensor_product(ctx, ins, attrs):
    """out[b,k] = x[b] @ W[k] @ y[b] + bias[k] (reference
    bilinear_tensor_product_op.cc)."""
    x, y, w = ins["X"][0], ins["Y"][0], ins["Weight"][0]
    out = jnp.einsum("bi,kij,bj->bk", x, w, y)
    if ins.get("Bias"):
        out = out + ins["Bias"][0]
    return {"Out": [out]}


@register("spectral_norm")
def spectral_norm(ctx, ins, attrs):
    """Weight / sigma_max via power iteration with carried U/V vectors
    (reference spectral_norm_op.cc)."""
    w, u, v = ins["Weight"][0], ins["U"][0], ins["V"][0]
    dim = int(attrs.get("dim", 0))
    iters = int(attrs.get("power_iters", 1))
    eps = float(attrs.get("eps", 1e-12))
    perm = [dim] + [i for i in range(w.ndim) if i != dim]
    mat = jnp.transpose(w, perm).reshape(w.shape[dim], -1)
    u = u.reshape(-1)
    v = v.reshape(-1)
    for _ in range(iters):
        v = mat.T @ u
        v = v / (jnp.linalg.norm(v) + eps)
        u = mat @ v
        u = u / (jnp.linalg.norm(u) + eps)
    sigma = u @ mat @ v
    return {"Out": [w / sigma]}


@register("histogram", stop_gradient=True, no_vjp_grad=True)
def histogram(ctx, ins, attrs):
    """Fixed-bin histogram (reference histogram_op.cc): min==max==0 uses
    the data's own range."""
    x = ins["X"][0].reshape(-1).astype(jnp.float32)
    bins = int(attrs.get("bins", 100))
    lo = float(attrs.get("min", 0))
    hi = float(attrs.get("max", 0))
    if lo == 0.0 and hi == 0.0:
        lo_v, hi_v = jnp.min(x), jnp.max(x)
    else:
        lo_v, hi_v = jnp.float32(lo), jnp.float32(hi)
    span = jnp.maximum(hi_v - lo_v, 1e-30)
    idx = jnp.clip(((x - lo_v) / span * bins).astype(jnp.int32), 0, bins - 1)
    inside = (x >= lo_v) & (x <= hi_v)
    out = jnp.zeros((bins,), jnp.int32).at[idx].add(inside.astype(jnp.int32))
    return {"Out": [out]}


@register("nonzero_static", stop_gradient=True, no_vjp_grad=True)
def nonzero_static(ctx, ins, attrs):
    """Static-shape nonzero: [numel, ndim] indices with the valid rows
    first (original order) and -1 padding, plus a scalar count."""
    x = ins["X"][0]
    flat = (x != 0).reshape(-1)
    numel = flat.shape[0]
    order = jnp.argsort(~flat, stable=True)  # nonzero positions first
    count = flat.sum().astype(jnp.int32)
    pos = jnp.where(jnp.arange(numel) < count, order, -1)
    idx = []
    rem = pos
    for dim in reversed(x.shape):
        idx.append(jnp.where(pos >= 0, rem % dim, -1))
        rem = rem // dim
    out = jnp.stack(idx[::-1], axis=1).astype(jnp.int32)
    return {"Out": [out], "Count": [count]}


@register("randperm", stop_gradient=True, no_vjp_grad=True)
def randperm(ctx, ins, attrs):
    """Random permutation of [0, n) (reference randperm_op.cc)."""
    from ..fluid.dtypes import convert_dtype

    n = int(attrs["n"])
    key = ctx.salted_rng(int(attrs.get("rng_salt", 0)))
    perm = jax.random.permutation(key, n)
    return {"Out": [perm.astype(runtime_dtype(attrs.get("dtype", "int64")))]}


@register("tanh_shrink")
def tanh_shrink(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [x - jnp.tanh(x)]}


@register("diag_embed")
def diag_embed(ctx, ins, attrs):
    """[..., N] -> [..., N, N] with the input on the main diagonal
    (reference diag_embed_op.cc, main-diagonal case)."""
    x = ins["X"][0]
    n = x.shape[-1]
    return {"Out": [x[..., None] * jnp.eye(n, dtype=x.dtype)]}


@register("precision_recall", stop_gradient=True, no_vjp_grad=True)
def precision_recall(ctx, ins, attrs):
    """Streaming multi-class precision/recall/F1 (reference
    operators/metrics/precision_recall_op.cc): Indices [N,1] predicted
    class, Labels [N,1], optional Weights [N,1]; StatesInfo [C,4] carries
    (TP, FP, TN, FN) per class across batches. Outputs BatchMetrics and
    AccumMetrics as [6]: macro-P, macro-R, macro-F1, micro-P, micro-R,
    micro-F1."""
    idx = ins["Indices"][0].reshape(-1).astype(jnp.int32)
    lbl = ins["Labels"][0].reshape(-1).astype(jnp.int32)
    w = (ins["Weights"][0].reshape(-1).astype(jnp.float32)
         if ins.get("Weights") else jnp.ones(idx.shape, jnp.float32))
    states = ins["StatesInfo"][0].astype(jnp.float32)  # [C, 4]
    c = states.shape[0]
    pred1 = jax.nn.one_hot(idx, c, dtype=jnp.float32) * w[:, None]
    lab1 = jax.nn.one_hot(lbl, c, dtype=jnp.float32) * w[:, None]
    tp = (pred1 * (idx == lbl)[:, None].astype(jnp.float32)).sum(0)
    fp = pred1.sum(0) - tp
    fn = lab1.sum(0) - tp
    tn = w.sum() - tp - fp - fn

    def metrics(tp_, fp_, fn_):
        prec = jnp.where(tp_ + fp_ > 0, tp_ / jnp.maximum(tp_ + fp_, 1e-10), 0.0)
        rec = jnp.where(tp_ + fn_ > 0, tp_ / jnp.maximum(tp_ + fn_, 1e-10), 0.0)
        f1 = jnp.where(prec + rec > 0,
                       2 * prec * rec / jnp.maximum(prec + rec, 1e-10), 0.0)
        return prec, rec, f1

    def six(tp_, fp_, fn_):
        p, r, f = metrics(tp_, fp_, fn_)
        mp, mr, mf = p.mean(), r.mean(), f.mean()
        up, ur, uf = metrics(tp_.sum(), fp_.sum(), fn_.sum())
        return jnp.stack([mp, mr, mf, up, ur, uf])

    batch = six(tp, fp, fn)
    new_states = states + jnp.stack([tp, fp, tn, fn], axis=1)
    accum = six(new_states[:, 0], new_states[:, 1], new_states[:, 3])
    return {"BatchMetrics": [batch], "AccumMetrics": [accum],
            "AccumStatesInfo": [new_states]}


def _tree_conv_coeffs(edges, n, max_depth):
    """Host-side tree2col coefficients (reference operators/math/
    tree2col.cc behavior, contract pinned by test_tree_conv_op.py's
    naive oracle): C[b, u, v, k] = eta_k of node v in node u's patch
    (nodes within `max_depth` hops, coefficients from depth and sibling
    position). Integer tree structure only — no gradients flow here."""
    import numpy as np

    edges = np.asarray(edges)
    b = edges.shape[0]
    out = np.zeros((b, n, n, 3), np.float32)
    for bi in range(b):
        children = [[] for _ in range(n + 2)]
        for p, c in edges[bi].tolist():
            if p >= 1:
                children[int(p)].append(int(c))

        for u in range(1, n + 1):
            # (node, idx-among-siblings, n-siblings, depth); a per-root
            # visited set (reference construct_patch) counts each node
            # once even with duplicate edges or multi-parent EdgeSets
            stack = [(u, 1, 1, 0)]
            visited = set()
            entries = []
            while stack:
                node, idx, l, depth = stack.pop()
                if node in visited:
                    continue
                visited.add(node)
                entries.append((node, idx, l, depth))
                if depth + 1 < max_depth:
                    ch = children[node]
                    for i, c in enumerate(ch, 1):
                        stack.append((c, i, len(ch), depth + 1))
            for node, idx, l, depth in entries:
                eta_t = float(max_depth - depth) / float(max_depth)
                eta_l = (1.0 - eta_t) * (
                    0.5 if l == 1 else float(idx - 1) / float(l - 1))
                eta_r = (1.0 - eta_t) * (1.0 - eta_l)
                out[bi, u - 1, node - 1, 0] += eta_l
                out[bi, u - 1, node - 1, 1] += eta_r
                out[bi, u - 1, node - 1, 2] += eta_t
    return out


@register("tree_conv")
def tree_conv(ctx, ins, attrs):
    """Tree-based convolution (TBCNN; reference tree_conv_op.cc): the
    data-dependent patch structure is built HOST-side from the integer
    EdgeSet (stop-gradient), and the learnable math is one einsum —
    fully differentiable wrt NodesVector and Filter on device."""
    nodes = ins["NodesVector"][0]          # [B, N, FS]
    edges = ins["EdgeSet"][0]              # [B, E, 2] int
    w = ins["Filter"][0]                   # [FS, 3, OUT, NF]
    max_depth = int(attrs.get("max_depth", 2))
    bsz, n, _fs = nodes.shape

    import functools as _ft

    coeffs = jax.pure_callback(
        _ft.partial(_tree_conv_coeffs, n=n, max_depth=max_depth),
        jax.ShapeDtypeStruct((bsz, n, n, 3), jnp.float32),
        edges,
    )
    coeffs = jax.lax.stop_gradient(coeffs)
    out = jnp.einsum("buvk,bvi,ikof->buof", coeffs,
                     nodes.astype(jnp.float32), w.astype(jnp.float32))
    return {"Out": [out.astype(nodes.dtype)]}


@register("tensor_stats", stop_gradient=True, no_vjp_grad=True)
def tensor_stats(ctx, ins, attrs):
    """Numerics observability reduction (telemetry/numerics.py,
    FLAGS_tensor_stats): one pass over X producing the (4,) float32
    vector [nan_count, inf_count, max_abs_finite, l2_finite]. Emitted
    next to the op that produced X, so XLA fuses it into the step and
    the host only pays the sampled device->host read of the stat var.
    max/l2 run over the FINITE elements (a single Inf must not flatten
    the rest of the series to Inf)."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    finite = jnp.isfinite(xf)
    nan_ct = jnp.sum(jnp.isnan(xf)).astype(jnp.float32)
    inf_ct = jnp.sum(jnp.isinf(xf)).astype(jnp.float32)
    safe = jnp.where(finite, xf, 0.0)
    max_abs = jnp.max(jnp.abs(safe)) if xf.size else jnp.float32(0.0)
    l2 = jnp.sqrt(jnp.sum(jnp.square(safe)))
    return {"Out": [jnp.stack([nan_ct, inf_ct,
                               jnp.asarray(max_abs, jnp.float32),
                               l2])]}
