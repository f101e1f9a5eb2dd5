"""Graph-level operator fusion passes over a Program.

Sibling of the AMP rewriter (contrib/mixed_precision/fp16_utils.py): a
pass walks a block's op list, pattern-matches, and rewrites in place
BEFORE append_backward runs, so the synthesized grad ops differentiate
the fused op directly (its emitter carries the custom-VJP Pallas
backward — ops/pallas/conv_bn.py).

conv+BN fusion (FLAGS_conv_bn_fusion): rewrites

    conv2d -> batch_norm [-> relu]

triples into one `fused_conv_bn` op when the intermediate activations
have no other consumer. The rewrite is semantics-preserving op-for-op:
the fused emitter reproduces the exact math of the unfused chain (f32
one-pass moments, running-stat update, relu), so with the flag off the
program — and with it the whole compiled step — is bit-identical to the
unfused baseline. Patterns the kernel cannot cover (grouped or dilated
convs, mismatched layouts, shared intermediates) are left untouched;
`is_test` BNs ARE rewritten — the emitter folds them into the conv
weights (one conv + bias add, no normalization pass).
"""
from __future__ import annotations

from typing import List

from . import framework
from .flags import flag


def _consumer_indices(block, name: str) -> List[int]:
    return [
        idx for idx, op in enumerate(block.ops) if name in op.input_names()
    ]


def _fusable_conv(op) -> bool:
    if op.type != "conv2d":
        return False
    if int(op.attr("groups", 1)) != 1:
        return False
    if tuple(op.attr("dilations", [1, 1])) != (1, 1):
        return False
    return True


def _exclusive_intermediate(block, name: str, consumer_idx: int) -> bool:
    """True when `name` is a plain SSA temporary read only by ops[consumer_idx]."""
    v = block._find_var_recursive(name)
    if v is None or v.persistable or v.is_data:
        return False
    return _consumer_indices(block, name) == [consumer_idx]


def _try_fuse_at(block, i) -> bool:
    conv = block.ops[i]
    if not _fusable_conv(conv):
        return False
    conv_out = conv.output("Output")
    if len(conv_out) != 1:
        return False
    conv_out = conv_out[0]
    users = _consumer_indices(block, conv_out)
    if len(users) != 1:
        return False
    j = users[0]
    bn = block.ops[j]
    if bn.type != "batch_norm" or bn.input("X") != [conv_out]:
        return False
    if not _exclusive_intermediate(block, conv_out, j):
        return False
    if bn.attr("data_layout", "NCHW") != conv.attr("data_format", "NCHW"):
        return False

    y = bn.output("Y")[0]
    relu_idx = None
    out_name = y
    yusers = _consumer_indices(block, y)
    if (
        len(yusers) == 1
        and block.ops[yusers[0]].type == "relu"
        and block.ops[yusers[0]].input("X") == [y]
        and _exclusive_intermediate(block, y, yusers[0])
    ):
        relu_idx = yusers[0]
        out_name = block.ops[relu_idx].output("Out")[0]

    attrs = {
        "strides": list(conv.attr("strides", [1, 1])),
        "paddings": list(conv.attr("paddings", [0, 0])),
        "dilations": list(conv.attr("dilations", [1, 1])),
        "groups": int(conv.attr("groups", 1)),
        "padding_algorithm": conv.attr("padding_algorithm", "EXPLICIT"),
        "data_format": conv.attr("data_format", "NCHW"),
        "epsilon": bn.attr("epsilon", 1e-5),
        "momentum": bn.attr("momentum", 0.9),
        "is_test": bn.attr("is_test", False),
        "use_global_stats": bn.attr("use_global_stats", False),
        "with_relu": relu_idx is not None,
    }
    dev = conv.attr("op_device")
    if dev is not None:
        attrs["op_device"] = dev
    cs = conv.attr(framework.OP_CALLSTACK_ATTR)
    if cs is not None:
        # diagnostics on the fused op point at the user's conv call
        attrs[framework.OP_CALLSTACK_ATTR] = cs

    fused = framework.Operator(
        block,
        "fused_conv_bn",
        inputs={
            "Input": list(conv.input("Input")),
            "Filter": list(conv.input("Filter")),
            "Scale": list(bn.input("Scale")),
            "Bias": list(bn.input("Bias")),
            "Mean": list(bn.input("Mean")),
            "Variance": list(bn.input("Variance")),
        },
        outputs={
            "Y": [out_name],
            "MeanOut": list(bn.output("MeanOut")),
            "VarianceOut": list(bn.output("VarianceOut")),
            "SavedMean": list(bn.output("SavedMean")),
            "SavedVariance": list(bn.output("SavedVariance")),
        },
        attrs=attrs,
    )
    fused.role = conv.role  # a rewrite takes the role of what it replaces
    for idx in sorted(filter(lambda k: k is not None, (i, j, relu_idx)),
                      reverse=True):
        del block.ops[idx]
    block.ops.insert(i, fused)
    for n in fused.output_names():
        v = block._find_var_recursive(n)
        if v is not None:
            v.op = fused
    # the exclusive intermediates the deleted ops produced (conv output,
    # and the BN Y when the relu folded in) now have neither producer nor
    # consumer; leaving them in block.vars kept stale Variable.op links
    # to the removed ops (proglint: stale-last-writer / unused-var)
    dead = [conv_out]
    if relu_idx is not None:
        dead.append(y)
    for n in dead:
        block.vars.pop(n, None)
    block.program._bump_version()
    return True


def apply_conv_bn_fusion(program) -> int:
    """Fuse every conv2d->batch_norm[->relu] triple in `program`.

    Returns the number of fusions performed. Unconditional (an explicit
    call states intent); the training wiring goes through
    `maybe_apply_conv_bn_fusion`, which honors FLAGS_conv_bn_fusion.

    Under FLAGS_program_verify the rewrite runs pass-sandwiched
    (fluid/analysis): the program is verified before and after, and any
    error finding the pass introduced raises attributed to it.
    """
    from .analysis import pass_sandwich

    fused = 0
    with pass_sandwich(program, "conv_bn_fusion"):
        for block in program.blocks:
            i = 0
            while i < len(block.ops):
                if _try_fuse_at(block, i):
                    fused += 1
                i += 1
    return fused


def maybe_apply_conv_bn_fusion(program) -> int:
    """Flag-gated entry used by Optimizer.backward / the AMP decorator.
    A no-op (zero rewrites, program untouched) unless FLAGS_conv_bn_fusion
    is set."""
    if not flag("FLAGS_conv_bn_fusion"):
        return 0
    return apply_conv_bn_fusion(program)
