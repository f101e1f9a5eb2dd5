"""Fused transformer encoder stack: lax.scan over stacked layer params.

TPU-native compile-time optimization the reference cannot express: its
ProgramDesc unrolls every encoder layer into separate ops
(python builders emit 12x the op list; the C++ executor interprets each),
whereas scanning over a leading layer axis of stacked parameters makes
XLA compile ONE layer body — compile time O(1) in depth, identical
steady-state FLOPs. Used by the flagship bench path; the unrolled
per-layer builder (models/bert.py encoder_layer) stays for parity and
per-layer tensor-parallel rules.

Parallel modes (attrs set by fleet; see fleet/__init__.py):
  sequence_parallel — ring attention over the "sp" mesh axis
  pipeline          — GPipe over the "pp" mesh axis: stacked layer params
                      are sharded on the layer dim (each stage owns L/pp
                      consecutive layers); the batch is split into
                      num_microbatches and activations flow stage-to-stage
                      with lax.ppermute inside a lax.scan over
                      M + pp - 1 ticks. The TPU-native replacement for the
                      reference's SectionWorker thread pipeline
                      (/root/reference/paddle/fluid/framework/section_worker.cc:82,
                       pipeline_trainer.cc:24) — same microbatch schedule,
                      but expressed as one differentiable XLA program.

Slots (all stacked on dim 0 = layer):
  Hidden [B,S,H], AttnBias [B,1,1,S],
  QKVW [L,H,3H], QKVB [L,3H], OutW [L,H,H], OutB [L,H],
  Ln1S/Ln1B [L,H], FfnW1 [L,H,F], FfnB1 [L,F], FfnW2 [L,F,H], FfnB2 [L,H],
  Ln2S/Ln2B [L,H]
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .registry import register

_PARAM_KEYS = (
    "QKVW", "QKVB", "OutW", "OutB", "Ln1S", "Ln1B",
    "FfnW1", "FfnB1", "FfnW2", "FfnB2", "Ln2S", "Ln2B",
)


def _policy_names(spec):
    """Parse a remat_policy attr: comma-separated checkpoint_name tags,
    with the shorthand 'flash' -> the kernel's saved residuals (o, lse).
    Tags available in the layer body: flash_o, flash_lse, attn_out,
    ln1_out, ffn_inter."""
    names = []
    for tok in str(spec).split(","):
        tok = tok.strip()
        if not tok:
            continue
        if tok == "flash":
            names += ["flash_o", "flash_lse"]
        else:
            names.append(tok)
    return tuple(dict.fromkeys(names))


def _act(name):
    return {
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
        "tanh": jnp.tanh,
        "silu": jax.nn.silu,
    }[name]


def _ln_f32(x, scale, shift, eps):
    """LayerNorm with f32 statistics regardless of compute dtype (bf16
    under AMP) — shared by the encoder and decoder stacks."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) \
        + shift.astype(jnp.float32)
    return y.astype(x.dtype)


def _add_ln(x, y, scale, shift, eps, mesh=None):
    """LayerNorm(x + y) — the residual+LN pair of both stacks. Dispatches
    the fused Pallas kernel (ops/pallas/add_ln.py; XLA's convert+reduce
    LN fusions measured ~30x the bandwidth roofline inside the encoder
    scan) with the identical-math jnp fallback. mesh: ctx.mesh, or None
    when already inside a shard_map (GPipe)."""
    from .pallas.add_ln import fused_add_ln, fused_ln_dispatch_ok

    if fused_ln_dispatch_ok(x.shape):
        return fused_add_ln(x, y, scale, shift, eps=eps, mesh=mesh)
    return _ln_f32(x + y, scale, shift, eps)


def _cheap_dropout(x, prob, key):
    """uint8 random bits: 4x less generator traffic than bernoulli's
    32-bit uniforms (profiled ~10ms/step on BERT-base with f32 masks).
    The threshold is quantized to 1/256, so rescale by the EFFECTIVE
    keep probability to stay unbiased."""
    thresh = max(1, min(255, round((1.0 - prob) * 256)))
    keep_eff = thresh / 256.0
    bits = jax.random.bits(key, x.shape, dtype=jnp.uint8)
    return jnp.where(bits < jnp.uint8(thresh), x / keep_eff, 0.0)


def _use_gpipe(ctx, attrs):
    return (
        bool(attrs.get("pipeline", False))
        and ctx.mesh is not None
        and "pp" in ctx.mesh.axis_names
        and ctx.mesh.shape["pp"] > 1
    )


@register("fused_encoder_stack")
def fused_encoder_stack(ctx, ins, attrs):
    hidden = ins["Hidden"][0]
    bias = ins.get("AttnBias", [None])[0]
    nh = int(attrs["num_heads"])
    act = _act(attrs.get("act", "gelu"))
    dropout_prob = float(attrs.get("dropout_prob", 0.0))
    attn_dropout_prob = float(attrs.get("attn_dropout_prob", 0.0))
    is_test = bool(attrs.get("is_test", False))
    eps = float(attrs.get("epsilon", 1e-5))
    use_flash = bool(attrs.get("use_flash_attention", True))
    from ..parallel import ring_attention as ring_mod

    ring = ring_mod.use_ring(ctx, attrs)
    mesh = ctx.mesh
    base_key = ctx.salted_rng(int(attrs.get("rng_salt", 0)))
    remat_policy = _policy_names(attrs.get("remat_policy", ""))
    if remat_policy:
        # the policy checkpoint wraps the whole layer; inner blanket
        # checkpoints would force recompute of values the policy elects
        # to save, so they are mutually exclusive
        attrs = dict(attrs)
        attrs["remat_ffn"] = attrs["remat_qkv"] = attrs["remat_layer"] = False

    stacked = {k: ins[k][0] for k in _PARAM_KEYS}

    def dropout(x, prob, key):
        if is_test or prob <= 0.0:
            return x
        return _cheap_dropout(x, prob, key)

    def make_layer(bias_arr, mb_salt=None, manual=False):
        """Layer body closed over a (possibly microbatch-sliced) attention
        bias; batch size is read from the carried hidden state. mb_salt
        (pipeline path) decorrelates dropout masks across microbatches.
        manual=True means we are already inside a shard_map (GPipe) and
        the Pallas kernels must not wrap themselves in another one."""

        def add_ln(x, y, scale, shift):
            return _add_ln(x, y, scale, shift, eps,
                           mesh=None if manual else mesh)

        def layer(carry, p):
            hid, idx = carry
            b, s, h = hid.shape
            dh = h // nh
            key = jax.random.fold_in(base_key, idx)
            if mb_salt is not None:
                key = jax.random.fold_in(key, mb_salt)
            k1, k2, k3 = jax.random.split(key, 3)

            # BSH fast path: the flash kernel reads q/k/v exactly as the
            # projection produces them ([B,S,H], heads sliced in-kernel
            # as static 64-lane views) — no head split/merge transposes,
            # which profiled at ~30-45 ms/step on BERT-base s512/b48.
            # Extreme lengths (whole-sequence VMEM residency won't fit)
            # and full [.., S, S] biases fall back to the streamed BHSD
            # kernel path below.
            from .pallas.flash_attention import bsh_dispatch_ok

            use_bsh = (
                (not ring) and use_flash
                and bsh_dispatch_ok(s, s, h, nh, bias=bias_arr, batch=b)
            )

            def project_qkv_flat(hid_, w, bias_):
                qkv = jnp.einsum("bsh,hk->bsk", hid_, w) + bias_
                return jnp.split(qkv, 3, axis=-1)

            def project_qkv(hid_, w, bias_):
                q_, k_, v_ = project_qkv_flat(hid_, w, bias_)

                def split_heads(x):
                    return x.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)

                return (split_heads(q_), split_heads(k_), split_heads(v_))

            if attrs.get("remat_qkv", False):
                # recompute the q/k/v projections in the backward instead
                # of stashing three [B,S,H] tensors per layer: one extra
                # qkv matmul per layer buys ~3x H*S*B bytes off the
                # residual stash (whose transposed-layout copies stall
                # the forward scan)
                project_qkv = jax.checkpoint(project_qkv)
                project_qkv_flat = jax.checkpoint(project_qkv_flat)

            if use_bsh:
                from .pallas.flash_attention import flash_attention_bsh

                q, k, v = project_qkv_flat(hid, p["QKVW"], p["QKVB"])
                ctx_l = flash_attention_bsh(
                    q, k, v, bias_arr, num_heads=nh,
                    dropout_prob=0.0 if is_test else attn_dropout_prob,
                    dropout_key=None if is_test else k1,
                    mesh=None if manual else mesh,
                )  # [B, S, H] — already merged
            elif ring:
                # sequence-parallel ring attention over "sp"; probs dropout
                # runs inside the ring. Outside a manual region the ring
                # wraps itself in shard_map (one ring schedule per layer
                # iteration); under GPipe (manual=True) we are ALREADY
                # inside the pipeline's shard_map, where every mesh axis
                # is bound — call the per-shard ring body directly (the
                # pp x sp composition: microbatches flow over "pp" while
                # each stage's attention rotates k/v over "sp")
                q, k, v = project_qkv(hid, p["QKVW"], p["QKVB"])
                key_bias = ring_mod.key_bias_from_attn_bias(bias_arr, b)
                if manual:
                    ctx_l = ring_mod.ring_attention(
                        q, k, v, "sp", bias=key_bias,
                        dropout_prob=0.0 if is_test else attn_dropout_prob,
                        dropout_key=None if is_test else k1,
                    )
                else:
                    ctx_l = ring_mod.ring_attention_global(
                        q, k, v, mesh, axis="sp", bias=key_bias,
                        batch_axis="dp",
                        dropout_prob=0.0 if is_test else attn_dropout_prob,
                        dropout_key=None if is_test else k1,
                    )
                ctx_l = ctx_l.transpose(0, 2, 1, 3).reshape(b, s, h)
            elif use_flash and _flash_ok(s, dh):
                # streamed BHSD kernel: serves the shapes BSH can't hold
                # resident (very long S) and full [.., S, S] biases
                from .pallas.flash_attention import flash_attention

                q, k, v = project_qkv(hid, p["QKVW"], p["QKVB"])
                ctx_l = flash_attention(
                    q, k, v, bias_arr,
                    dropout_prob=0.0 if is_test else attn_dropout_prob,
                    dropout_key=None if is_test else k1,
                    mesh=None if manual else mesh,
                )
                ctx_l = ctx_l.transpose(0, 2, 1, 3).reshape(b, s, h)
            else:
                q, k, v = project_qkv(hid, p["QKVW"], p["QKVB"])
                scores = jnp.einsum(
                    "bnqd,bnkd->bnqk", q, k,
                    preferred_element_type=jnp.float32,
                ) / math.sqrt(dh)
                if bias_arr is not None:
                    scores = scores + bias_arr.astype(scores.dtype)
                probs = jax.nn.softmax(scores, axis=-1).astype(hid.dtype)
                probs = dropout(probs, attn_dropout_prob, k1)
                ctx_l = jnp.einsum("bnqk,bnkd->bnqd", probs, v)
                # tag the fallback path's context too so remat_policy
                # behaves the same when the kernel doesn't dispatch (the
                # kernel path tags o/lse inside its custom-vjp forward)
                ctx_l = checkpoint_name(ctx_l, "flash_o")
                ctx_l = ctx_l.transpose(0, 2, 1, 3).reshape(b, s, h)

            attn_out = jnp.einsum("bsh,hk->bsk", ctx_l, p["OutW"]) + p["OutB"]
            attn_out = checkpoint_name(
                dropout(attn_out, dropout_prob, k2), "attn_out"
            )
            hid = checkpoint_name(
                add_ln(hid, attn_out, p["Ln1S"], p["Ln1B"]), "ln1_out"
            )

            def ffn(h_, w1, b1, w2, b2, key3):
                inter = checkpoint_name(
                    act(jnp.einsum("bsh,hf->bsf", h_, w1) + b1), "ffn_inter"
                )
                out_ = jnp.einsum("bsf,fh->bsh", inter, w2) + b2
                return dropout(out_, dropout_prob, key3)

            if attrs.get("remat_ffn", False):
                # recompute `inter` ([B,S,F], the largest activation) in
                # the backward instead of saving it: ~1/3 extra fwd FLOPs
                # for this block buys ~F/H x memory off the residuals,
                # unlocking larger batches
                ffn = jax.checkpoint(ffn)
            ffn_out = ffn(hid, p["FfnW1"], p["FfnB1"], p["FfnW2"], p["FfnB2"], k3)
            hid = add_ln(hid, ffn_out, p["Ln2S"], p["Ln2B"])
            return (hid, idx + 1), None

        return layer

    if remat_policy and not _use_gpipe(ctx, attrs):
        # policy remat: save ONLY the tagged values (e.g. the flash
        # kernel's o/lse residuals) per layer; everything untagged — the
        # qkv/out/ffn projections, norms, dropouts — is recomputed in the
        # backward from the scan-carried hidden. With 'flash' saved the
        # recompute DCEs the forward attention kernel, unlike remat_layer
        # which re-runs it: the long-context (s>=4096) memory/FLOPs
        # sweet spot, and it also kills the q/k/v residual-stash layout
        # copies that stalled the forward scan at s512.
        _layer = make_layer(bias)
        pol = jax.checkpoint_policies.save_only_these_names(*remat_policy)
        layer_ck = jax.checkpoint(lambda c, p: _layer(c, p), policy=pol)
        (out, _), _ = jax.lax.scan(layer_ck, (hidden, jnp.int32(0)), stacked)
        return {"Out": [out]}

    if attrs.get("remat_layer", False) and not _use_gpipe(ctx, attrs):
        # full-layer remat: save only the carried hidden per layer
        _layer = make_layer(bias)
        layer_ck = jax.checkpoint(lambda c, p: _layer(c, p))
        (out, _), _ = jax.lax.scan(layer_ck, (hidden, jnp.int32(0)), stacked)
        return {"Out": [out]}

    if _use_gpipe(ctx, attrs):
        M = int(attrs.get("num_microbatches", 0)) or mesh.shape["pp"]
        ml = make_layer
        if remat_policy:
            # the policy wraps each stage-local layer body inside the
            # GPipe shard_map, so pipeline + remat_policy saves only the
            # tagged values per layer (same contract as the scan path)
            pol = jax.checkpoint_policies.save_only_these_names(
                *remat_policy)

            def ml(bias_arr, mb_salt=None, manual=False):
                inner = make_layer(bias_arr, mb_salt, manual)
                return jax.checkpoint(
                    lambda c, p: inner(c, p), policy=pol)
        out = _gpipe_stack(hidden, stacked, bias, mesh, M, ml,
                           ring=ring)
        return {"Out": [out]}

    layer = make_layer(bias)
    (out, _), _ = jax.lax.scan(layer, (hidden, jnp.int32(0)), stacked)
    return {"Out": [out]}


def _gpipe_stack(hidden, stacked, bias, mesh, M, make_layer, ring=False):
    """GPipe schedule over the "pp" axis. Stage s owns layers
    [s*L/pp, (s+1)*L/pp); microbatch m enters stage 0 at tick m and leaves
    stage pp-1 at tick m+pp-1. Activations rotate via ppermute; the
    attention bias is replicated over pp, so each stage just indexes the
    microbatch it is currently processing (m = t - s) — no transfer.
    ring=True additionally shards the SEQUENCE dim over "sp" (hidden and
    the per-key bias); the layer body then runs ring attention inside
    this shard_map (pp x sp composition for long-context pipelines)."""
    from jax import lax

    from jax.sharding import PartitionSpec as P

    npp = mesh.shape["pp"]
    dp = "dp" if "dp" in mesh.axis_names else None
    dp_size = mesh.shape[dp] if dp else 1
    sp = (
        "sp" if ring and "sp" in mesh.axis_names and mesh.shape["sp"] > 1
        else None
    )
    L = stacked["QKVW"].shape[0]
    if L % npp != 0:
        raise ValueError(f"num layers {L} must divide by pp={npp}")
    B = hidden.shape[0]
    if B % (dp_size * M) != 0:
        raise ValueError(
            f"per-dp-shard batch {B}//{dp_size} must divide by "
            f"num_microbatches={M}"
        )

    keys = list(_PARAM_KEYS)
    hid_spec = P(dp, sp, None)
    bias_spec = P(dp, None, None, sp)
    p_specs = tuple(P("pp") for _ in keys)
    perm = [(i, i + 1) for i in range(npp - 1)]

    def body(hid_l, bias_l, *p_locals):
        s_idx = lax.axis_index("pp")
        l_loc = L // npp
        b_loc = hid_l.shape[0]
        mb = b_loc // M
        mbs = hid_l.reshape(M, mb, *hid_l.shape[1:])
        bias_mbs = (
            bias_l.reshape(M, mb, *bias_l.shape[1:]) if bias_l is not None else None
        )
        p_local = dict(zip(keys, p_locals))

        def stage(x, bias_x, mb_salt):
            layer = make_layer(bias_x, mb_salt, manual=True)
            start = s_idx * l_loc
            (out, _), _ = lax.scan(layer, (x, start), p_local)
            return out

        def tick(carry, t):
            recv_x = carry
            # the microbatch this stage works on at tick t (bubble ticks
            # clamp to a valid index; their output is discarded)
            m_cur = jnp.clip(t - s_idx, 0, M - 1)
            x0 = lax.dynamic_index_in_dim(mbs, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            x_in = jnp.where(s_idx == 0, x0, recv_x)
            b_in = (
                lax.dynamic_index_in_dim(bias_mbs, m_cur, 0, keepdims=False)
                if bias_mbs is not None
                else None
            )
            out = stage(x_in, b_in, m_cur)
            send_x = lax.ppermute(out, "pp", perm)
            emit = jnp.logical_and(s_idx == npp - 1, t >= npp - 1)
            y = jnp.where(emit, out, jnp.zeros_like(out))
            return send_x, y

        _, ys = lax.scan(tick, jnp.zeros_like(mbs[0]), jnp.arange(M + npp - 1))
        # microbatch m finishes at tick m + npp - 1 (on the last stage)
        out_l = ys[npp - 1:].reshape(b_loc, *hid_l.shape[1:])
        # only the last stage holds nonzero output; psum broadcasts it
        return lax.psum(out_l, "pp")

    if bias is None:
        def body_nobias(hid_l, *p_locals):
            return body(hid_l, None, *p_locals)

        return jax.shard_map(
            body_nobias, mesh=mesh, in_specs=(hid_spec,) + p_specs,
            out_specs=hid_spec, check_vma=False,
        )(hidden, *[stacked[k] for k in keys])

    return jax.shard_map(
        body, mesh=mesh, in_specs=(hid_spec, bias_spec) + p_specs,
        out_specs=hid_spec, check_vma=False,
    )(hidden, bias, *[stacked[k] for k in keys])


def _flash_ok(s, dh):
    from .pallas.flash_attention import flash_shapes_ok

    return flash_shapes_ok(s, dh)


_DEC_PARAM_KEYS = (
    "SelfQKVW", "SelfQKVB", "SelfOutW", "SelfOutB", "Ln1S", "Ln1B",
    "CrossQW", "CrossQB", "CrossKW", "CrossKB", "CrossVW", "CrossVB",
    "CrossOutW", "CrossOutB", "Ln2S", "Ln2B",
    "FfnW1", "FfnB1", "FfnW2", "FfnB2", "Ln3S", "Ln3B",
)


@register("fused_decoder_stack")
def fused_decoder_stack(ctx, ins, attrs):
    """Scan-fused transformer DECODER stack (causal self-attention +
    cross-attention over a loop-invariant encoder memory + FFN, post-LN):
    the NMT counterpart of fused_encoder_stack. The reference builds all
    6 decoder layers as separate op lists (dist_transformer.py); one
    scanned body compiles once, and both attentions run the BSH
    (transpose-free) Pallas flash kernel — causal masking in-kernel for
    self-attention, and RECTANGULAR (St != Ss) cross-attention with the
    source padding mask as a per-key bias. Under sequence parallelism
    ("sp" mesh axis): self-attention runs the causal ring over trg
    shards; cross-attention keeps the jnp composition on global arrays
    so GSPMD all-gathers the src-sharded k/v (Megatron-SP strategy).

    Slots (stacked on dim 0 = layer): _DEC_PARAM_KEYS above; inputs
    Hidden [B,St,H], EncOut [B,Ss,H], SrcBias [B,1,1,Ss]."""
    hidden = ins["Hidden"][0]
    enc_out = ins["EncOut"][0]
    src_bias = ins.get("SrcBias", [None])[0]
    nh = int(attrs["num_heads"])
    act = _act(attrs.get("act", "relu"))
    dropout_prob = float(attrs.get("dropout_prob", 0.0))
    attn_dropout_prob = float(attrs.get("attn_dropout_prob", 0.0))
    is_test = bool(attrs.get("is_test", False))
    eps = float(attrs.get("epsilon", 1e-5))
    use_flash = bool(attrs.get("use_flash_attention", True))
    from ..parallel import ring_attention as ring_mod

    # sequence parallelism: causal self-attention runs the ring over
    # "sp" (trg tokens sharded; k/v blocks rotate via ppermute); the
    # rectangular cross-attention keeps the jnp composition on GLOBAL
    # arrays — under GSPMD the trg dim stays sp-sharded and XLA
    # all-gathers the (src-sharded) k/v projections, the Megatron-SP
    # strategy for attending over a full memory from a sharded query
    ring = ring_mod.use_ring(ctx, attrs)
    mesh = ctx.mesh
    base_key = ctx.salted_rng(int(attrs.get("rng_salt", 0)))
    stacked = {k: ins[k][0] for k in _DEC_PARAM_KEYS}

    def add_ln(x, y, scale, shift):
        return _add_ln(x, y, scale, shift, eps, mesh=mesh)

    def dropout(x, prob, key):
        if is_test or prob <= 0.0:
            return x
        return _cheap_dropout(x, prob, key)

    b, st, h = hidden.shape
    ss = enc_out.shape[1]
    dh = h // nh

    def split_heads(x, s):
        return x.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)

    def merge_heads(x, s):
        return x.transpose(0, 2, 1, 3).reshape(b, s, h)

    def jnp_attn(q, k, v, bias4, causal, key):
        scores = jnp.einsum(
            "bnqd,bnkd->bnqk", q, k, preferred_element_type=jnp.float32,
        ) / math.sqrt(dh)
        if bias4 is not None:
            scores = scores + bias4.astype(scores.dtype)
        if causal:
            qlen, klen = scores.shape[-2], scores.shape[-1]
            cm = jnp.arange(qlen)[:, None] >= jnp.arange(klen)[None, :]
            scores = jnp.where(cm, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        probs = dropout(probs, attn_dropout_prob, key)
        return jnp.einsum("bnqk,bnkd->bnqd", probs, v)

    from .pallas.flash_attention import bsh_dispatch_ok

    def attend_flat(q3, k3, v3, bias4, causal, key):
        """q3 [B,Sq,H], k3/v3 [B,Skv,H] -> [B,Sq,H]. BSH kernel when the
        shapes allow — rectangular (cross-attention) included, no head
        transposes; jnp composition otherwise."""
        sq, skv = q3.shape[1], k3.shape[1]
        if ring and causal:
            # trg-sharded causal self-attention over the ring
            ctx4 = ring_mod.ring_attention_global(
                split_heads(q3, sq), split_heads(k3, skv),
                split_heads(v3, skv), mesh, axis="sp", causal=True,
                batch_axis="dp",
                dropout_prob=0.0 if is_test else attn_dropout_prob,
                dropout_key=None if is_test else key,
            )
            return merge_heads(ctx4, sq)
        if ring:
            # cross-attention under sp: jnp path — GSPMD gathers k/v
            ctx4 = jnp_attn(split_heads(q3, sq), split_heads(k3, skv),
                            split_heads(v3, skv), bias4, False, key)
            return merge_heads(ctx4, sq)
        if use_flash and bsh_dispatch_ok(sq, skv, h, nh, bias=bias4,
                                         batch=b, causal=causal):
            from .pallas.flash_attention import flash_attention_bsh

            return flash_attention_bsh(
                q3, k3, v3, bias4, num_heads=nh, causal=causal,
                dropout_prob=0.0 if is_test else attn_dropout_prob,
                dropout_key=None if is_test else key,
                mesh=mesh,
            )
        ctx4 = jnp_attn(split_heads(q3, sq), split_heads(k3, skv),
                        split_heads(v3, skv), bias4, causal, key)
        return merge_heads(ctx4, sq)

    def layer(carry, p):
        hid, idx = carry
        key = jax.random.fold_in(base_key, idx)
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)

        # --- causal self-attention
        qkv = jnp.einsum("bsh,hk->bsk", hid, p["SelfQKVW"]) + p["SelfQKVB"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        ctx_s = attend_flat(q, k, v, None, True, k1)
        self_out = jnp.einsum(
            "bsh,hk->bsk", ctx_s, p["SelfOutW"]
        ) + p["SelfOutB"]
        hid = add_ln(hid, dropout(self_out, dropout_prob, k2),
                     p["Ln1S"], p["Ln1B"])

        # --- cross-attention over the encoder memory (rectangular: trg
        # queries over src keys — in-kernel via the BSH layout)
        qc = jnp.einsum("bsh,hk->bsk", hid, p["CrossQW"]) + p["CrossQB"]
        kc = jnp.einsum("bsh,hk->bsk", enc_out, p["CrossKW"]) + p["CrossKB"]
        vc = jnp.einsum("bsh,hk->bsk", enc_out, p["CrossVW"]) + p["CrossVB"]
        ctx_c = attend_flat(qc, kc, vc, src_bias, False, k3)
        cross_out = jnp.einsum(
            "bsh,hk->bsk", ctx_c, p["CrossOutW"]
        ) + p["CrossOutB"]
        hid = add_ln(hid, dropout(cross_out, dropout_prob, k4),
                     p["Ln2S"], p["Ln2B"])

        # --- FFN
        def ffn(h_, w1, b1, w2, b2, key5):
            inter = act(jnp.einsum("bsh,hf->bsf", h_, w1) + b1)
            out_ = jnp.einsum("bsf,fh->bsh", inter, w2) + b2
            return dropout(out_, dropout_prob, key5)

        if attrs.get("remat_ffn", False):
            ffn = jax.checkpoint(ffn)
        ffn_out = ffn(hid, p["FfnW1"], p["FfnB1"], p["FfnW2"], p["FfnB2"], k5)
        hid = add_ln(hid, ffn_out, p["Ln3S"], p["Ln3B"])
        return (hid, idx + 1), None

    (out, _), _ = jax.lax.scan(layer, (hidden, jnp.int32(0)), stacked)
    return {"Out": [out]}
