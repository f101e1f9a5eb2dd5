"""AMP graph rewrite: insert casts around white/black-listed ops.

Parity: /root/reference/python/paddle/fluid/contrib/mixed_precision/fp16_utils.py
(rewrite_program:190, update_loss_scaling helpers :333).

TPU-native notes: the low-precision dtype defaults to bfloat16 (MXU
native; no loss scaling needed). float16 is kept for parity and uses the
same dynamic loss scaling protocol as the reference. Master weights are
implicit: parameters stay float32 and are cast at use — the cast's vjp
accumulates gradients back in float32, which is exactly the
master-weight contract.
"""
from __future__ import annotations

from typing import Set

from ...fluid import framework
from ...fluid.dtypes import convert_dtype


def _is_float(dtype) -> bool:
    import numpy as np

    return np.dtype(dtype).kind == "f" and np.dtype(dtype).itemsize >= 2


def rewrite_program(program, amp_lists, dest_dtype="bfloat16"):
    """Walk block-0 ops; before each white op insert casts of its float32
    inputs to `dest_dtype`, before each black op casts of low-precision
    inputs back to float32. Shapes/dtypes of downstream vars are re-inferred
    op by op as the rewrite proceeds."""
    import numpy as np

    block = program.global_block()
    dest = convert_dtype(dest_dtype)
    f32 = np.dtype("float32")

    # walk in program order, re-inferring each op after its (possible)
    # input rewiring — downstream cast decisions then see current dtypes
    # (a white op's bf16 output decides where black-op casts fire)
    i = 0
    while i < len(block.ops):
        op = block.ops[i]
        if op.type == "cast":
            i += 1
            continue
        if op.type in amp_lists.white_list:
            i += _cast_op_inputs(block, i, op, want=dest, source_kind=f32)
        elif op.type in amp_lists.black_list:
            i += _cast_op_inputs(block, i, op, want=f32, source_kind=dest)
        framework.infer_op_outputs(block, op)
        i += 1
    program._amp_enabled = True
    program._bump_version()


# input slots AMP must NEVER down-cast on white-listed ops: running
# statistics and affine params whose f32 state is written back each step
# (casting Mean/Variance would quantize the moving averages to bf16
# every step, and an is_test pass would clobber the f32 stat params)
_KEEP_F32_SLOTS = {
    "batch_norm": {"Mean", "Variance", "Scale", "Bias"},
    "fused_conv_bn": {"Mean", "Variance", "Scale", "Bias"},
    "layer_norm": {"Scale", "Bias"},
    "rms_norm": {"Scale"},
    "short_conv": {"Filter"},
    # the router scores in f32 over its own f32 weights; the selection
    # bias is a buffer that is compared, never multiplied
    "moe_swiglu": {"GateW", "ExpertBias"},
    "mla": {"QANorm", "KVANorm"},
    # the mappings are float32 end to end: their weights in, the three H
    # out, and the H into the mixing
    "mhc_map": {"Phi", "Bias", "Alpha"},
    "mhc_pre": {"HPre"},
    "mhc_post": {"HRes", "HPost"},
    "mamba2": {"ConvW", "ConvB", "DtBias", "ALog", "D", "NormW"},
}


def _cast_op_inputs(block, idx, op, want, source_kind) -> int:
    """Insert cast ops before block.ops[idx] for inputs of dtype
    source_kind; rewires op inputs. Returns #ops inserted."""
    import numpy as np

    from ...fluid import unique_name

    keep = _KEEP_F32_SLOTS.get(op.type, ())
    inserted = 0
    for slot, names in list(op.inputs.items()):
        if slot in keep and np.dtype(want) != np.dtype(np.float32):
            continue
        new_names = []
        for n in names:
            v = block._find_var_recursive(n)
            if v is None or v.dtype is None or np.dtype(v.dtype) != np.dtype(source_kind):
                new_names.append(n)
                continue
            cast_name = unique_name.generate(f"{n}.cast_{np.dtype(want).name}")
            block.create_var(
                name=cast_name, shape=v.shape, dtype=want, stop_gradient=v.stop_gradient
            )
            block._insert_op(
                idx + inserted,
                type="cast",
                inputs={"X": [n]},
                outputs={"Out": [cast_name]},
                attrs={"in_dtype": v.dtype, "out_dtype": np.dtype(want)},
                infer=False,
            )
            new_names.append(cast_name)
            inserted += 1
        op.inputs[slot] = new_names
    return inserted


def cast_parameters_to_bf16(program):  # parity helper (reference fp16_utils)
    raise NotImplementedError(
        "parameters stay float32 (implicit master weights); pure-bf16 "
        "serving uses save_inference_model + a bf16 rewrite of the pruned graph"
    )
