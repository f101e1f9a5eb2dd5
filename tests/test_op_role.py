"""Every op knows its role, and the compiled step says so.

The programs are the benchmark's rehearsal programs, built the way
`benchmark/harness.build_program` builds them (bf16 AMP, `minimize`, `fleet`
over a virtual `dp=4` mesh for one case), plus the same BERT program under
float16 AMP, whose dynamic loss scaling puts the unscale, the finite check
and the scale update between the gradients and the update.
"""
import contextlib
import functools
import re
from unittest import mock

import pytest

import paddle_tpu.fluid as fluid
from benchmark import harness, manifest, roles
from paddle_tpu.contrib import mixed_precision
from paddle_tpu.fluid import framework
from paddle_tpu.fluid.executor import Scope
from paddle_tpu.fluid.layers import nn
from paddle_tpu.ops import registry
from paddle_tpu.telemetry import cost

PROGRAMS = ["bert-base.s512", "resnet50.train224", "bert-base.s512.dp4"]
FP16 = "bert-base.s512/fp16"
ORDER = list(framework.ROLES)
# op_names of the step's own name stack that carry no role, each for a reason
NO_ROLE = (
    # the executor's RNG advance, after the last op (Executor._compile)
    "jit(step)/jit(_threefry_fold_in)",
    # loop invariants of the encoder stack's scan (the attention mask's cast,
    # the dropout's zeros): jax's partial evaluation of the scan hoists them
    # out of the loop and names them without the scope the scan was traced in
    "jit(step)/convert_element_type",
    "jit(step)/jit(_where)",
)


def _build(name):
    """A fresh program of the case, from the same state of the process-wide
    dropout salt (it ends up as a constant in the step)."""
    cell = manifest.load_cell(manifest.load_manifest(), name.split("/")[0],
                              rehearse=True)
    decorate = mixed_precision.decorate
    patches = [mock.patch.object(nn, "_rng_salt_counter", [0])]
    if name == FP16:
        patches.append(mock.patch.object(
            mixed_precision, "decorate",
            lambda opt, use_bf16=True: decorate(opt, use_bf16=False)))
    with contextlib.ExitStack() as stack:
        for p in patches:
            stack.enter_context(p)
        return cell, harness.build_program(
            cell, int(cell.traffic["batch"]), dropout=True, seed=3)


def _lower(cell, built):
    exe, scope = fluid.Executor(), Scope()
    exe.run(built.startup, scope=scope)
    batch = int(cell.traffic["batch"])
    feed = cell.family.make_batch(cell.config, cell.traffic, batch,
                                  harness.batch_rng(3, 1, 0))
    return exe._lower_step(built.main, feed=feed, fetch_list=[built.loss],
                           scope=scope)


@functools.lru_cache(maxsize=None)
def _step_texts(name, scoped=True):
    """The case's step as XLA gets it (StableHLO, printed without
    locations) and as XLA leaves it (the optimized HLO): as shipped, or
    with the role scope replaced by a null context."""
    cell, built = _build(name)
    patch = (contextlib.nullcontext() if scoped else mock.patch.object(
        registry, "role_scope", lambda role: contextlib.nullcontext()))
    with patch:
        lowered = _lower(cell, built)
    return lowered.as_text(), lowered.compile().as_text()


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("name", PROGRAMS + [FP16])
def test_roles_run_forward_backward_optimizer_in_program_order(name):
    _, built = _build(name)
    ops = built.main.global_block().ops
    for block in built.main.blocks:
        for op in block.ops:
            assert op.role in ORDER, op
            assert "role" not in op.attrs and "op_role" not in op.attrs
    # forward* backward* optimizer*: no op breaks the order in these
    # programs, so none is excused here
    ranks = [ORDER.index(op.role) for op in ops]
    assert ranks == sorted(ranks)
    assert set(ranks) == {0, 1, 2}

    first_backward = ranks.index(1)
    fill = ops[first_backward]
    assert fill.type == "fill_constant"
    assert fill.output("Out") == [ops[first_backward - 1].output("Out")[0]
                                  + framework.GRAD_VAR_SUFFIX]
    first_grad = next(op for op in ops if op.type.endswith("_grad"))
    assert first_grad.role == "backward"
    assert all(op.role == "backward" for op in ops
               if op.type.endswith("_grad") or op.type == "sum")

    updates = [op for op in ops if op.input("Param") and op.output("ParamOut")]
    assert len(updates) == len(built.grad_of)
    assert all(op.role == "optimizer" for op in updates)
    # the start-up program initialises; it has no backward and no update
    assert {op.role for op in built.startup.global_block().ops} == {"forward"}

    scaling = [op for op in ops
               if any(re.match(r"(loss_scaling|good_steps|bad_steps)_\d+$", n)
                      for n in op.input_names() + op.output_names())]
    # the loss is scaled going forward and the scale's gradient op is part
    # of the backward pass; what else touches the scale is the optimizer's
    assert [(op.type, op.role) for op in scaling[:2]] == [
        ("elementwise_mul", "forward"), ("elementwise_mul_grad", "backward")]
    if name == FP16:
        # unscale, count the good and bad steps, rescale
        assert len(scaling) > 6
        assert all(op.role == "optimizer" for op in scaling[2:])
        checks = [op for op in ops if op.type == "isfinite_v2"]
        assert checks and all(op.role == "optimizer" for op in checks)
    else:
        # bf16 keeps the scale at 1 and has no unscale pass
        assert len(scaling) == 2


@pytest.mark.parametrize("name", PROGRAMS + [FP16])
def test_clone_keeps_every_role(name):
    _, built = _build(name)
    for clone in (built.main.clone(), built.main.clone(for_test=True)):
        for block, cloned in zip(built.main.blocks, clone.blocks):
            assert ([(op.type, op.role) for op in block.ops]
                    == [(op.type, op.role) for op in cloned.ops])
        # the clone is a program like any other: a new op is forward
        op = clone.global_block().append_op(
            type="fill_constant", outputs={"Out": ["role_probe"]},
            attrs={"shape": [1], "dtype": "float32", "value": 0.0})
        assert op.role == "forward"


def test_clone_keeps_the_roles_of_recompute_sub_ops():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [4, 8], append_batch_size=False)
        h = fluid.layers.fc(x, 8, act="relu")
        h2 = fluid.layers.fc(h, 8, act="relu")
        loss = fluid.layers.reduce_mean(fluid.layers.fc(h2, 1))
        opt = fluid.optimizer.RecomputeOptimizer(fluid.optimizer.SGD(0.1))
        opt._set_checkpoints([h2])
        opt.minimize(loss)
    segments = [op for op in main.global_block().ops
                if op.type == "recompute_segment"]
    assert segments and all(op.role == "forward" for op in segments)
    for op in segments:  # as if a later pass had marked them
        for sub in op.attrs["recompute_sub_ops"]:
            sub.role = "backward"
    cloned = [op for op in main.clone().global_block().ops
              if op.type == "recompute_segment"]
    assert cloned and all(
        sub.role == "backward"
        for op in cloned for sub in op.attrs["recompute_sub_ops"])


def test_guards_nest_and_restore():
    program = fluid.Program()
    block = program.global_block()

    def probe():
        return block.append_op(
            type="fill_constant", outputs={"Out": ["v"]},
            attrs={"shape": [1], "dtype": "float32", "value": 0.0}).role

    assert probe() == "forward"
    with program._backward_role_guard():
        assert probe() == "backward"
        with program._optimized_guard():
            assert probe() == "optimizer"
            assert block._insert_op(
                0, type="fill_constant", outputs={"Out": ["w"]},
                attrs={"shape": [1], "dtype": "float32",
                       "value": 0.0}).role == "optimizer"
        assert probe() == "backward"
    with pytest.raises(RuntimeError):
        with program._optimized_guard():
            raise RuntimeError("inside")
    assert probe() == "forward"


@pytest.mark.parametrize("name", PROGRAMS)
def test_every_instruction_of_the_step_resolves_to_a_role(name):
    names = [n for n in _op_names(_step_texts(name)[1])
             if n.startswith("jit(step)/")]  # the step's own name stack
    found = {role: 0 for role in ORDER}
    for op_name in names:
        role = roles.role_of(op_name)
        if role is None:
            assert op_name.startswith(NO_ROLE), op_name
        else:
            found[role] += 1
    assert all(found.values()), found
    # transposes and recomputation are emitted at the grad op
    backward = [n for n in names if "transpose(jvp(" in n
                or "rematted_computation" in n]
    assert backward
    assert {roles.role_of(n) for n in backward} == {"backward"}
    assert {roles.role_of(n) for n in names
            if "/jvp(" in n and "transpose(" not in n} == {"forward"}


@pytest.mark.parametrize("name", PROGRAMS)
def test_role_scopes_leave_the_executable_alone(name):
    """What XLA is given is the same text byte for byte with the role
    scopes and without, once printed without locations; and what XLA makes
    of it, the optimized HLO with its metadata stripped (each instruction's
    `metadata={...}` and the tables of source locations they point into),
    is the same too. XLA:CPU numbers an instruction or two differently from
    one compile to the next of the very same input (`convert.167`,
    `convert.169` in the ResNet step), so names are compared by the order
    in which they first appear."""
    def stripped(text):
        text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
        text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                      r"\n(\d+ .*\n)*", "\n", text)
        seen = {}
        return re.sub(r"%[A-Za-z0-9_.\-]+",
                      lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"),
                      text)

    (given, scoped), (given_bare, bare) = (
        _step_texts(name), _step_texts(name, scoped=False))
    assert any(roles.role_of(n) for n in _op_names(scoped))
    assert not any(roles.role_of(n) for n in _op_names(bare))
    assert given == given_bare
    assert "metadata=" not in stripped(scoped)
    assert stripped(scoped) == stripped(bare)


@pytest.mark.parametrize("name", PROGRAMS)
def test_op_profile_scope_nests_inside_the_role(name):
    cell, built = _build(name)
    fluid.flags.set_flags({"FLAGS_op_profile": True})
    try:
        text = _lower(cell, built).compile().as_text()
    finally:
        fluid.flags.set_flags({"FLAGS_op_profile": False})
    ops = built.main.global_block().ops
    seen = set()
    for op_name in _op_names(text):
        scope = cost.extract_scope(op_name)
        if scope is None or not op_name.startswith("jit(step)/"):
            continue
        index, op_type = scope
        assert ops[index].type == op_type
        # the op's scope sits inside the scope of the op's role
        assert op_name.split("/")[1:3] == [
            ops[index].role, f"op{index}:{op_type}"], op_name
        seen.add(ops[index].role)
    assert seen == set(ORDER)


# ---------------------------------------------------------------------------
# name scopes: a model's own parts, beneath the role
# ---------------------------------------------------------------------------


def _scoped_program(scoped=True):
    """Two `fc` layers, the second inside `head` and its bias inside
    `head/bias` (or inside nothing), and their loss, minimized."""
    from paddle_tpu.fluid import layers

    scope_of = (fluid.name_scope if scoped
                else lambda name: contextlib.nullcontext())
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", shape=[4, 8], dtype="float32",
                        append_batch_size=False)
        h = layers.fc(x, 8, act="relu", bias_attr=False)
        with scope_of("head"):
            y = layers.fc(h, 3, bias_attr=False)
            with scope_of("bias"):
                y = layers.scale(y, 2.0, bias=1.0)
        loss = layers.reduce_mean(layers.square(y))
        fluid.optimizer.SGD(0.1).minimize(loss, startup_program=startup)
    return main, startup, loss


def test_ops_and_their_gradient_ops_carry_the_name_scopes():
    main, _, _ = _scoped_program()
    ops = main.global_block().ops
    by_type = {op.type: op for op in ops}
    assert by_type["relu"].scope == () == by_type["reduce_mean"].scope
    assert by_type["scale"].scope == ("head", "bias")
    assert by_type["scale_grad"].scope == ("head", "bias")
    assert by_type["scale_grad"].role == framework.ROLE_BACKWARD
    muls = [op for op in ops if op.type.startswith("mul")]
    assert [(op.type, op.scope) for op in muls] == [
        ("mul", ()), ("mul", ("head",)), ("mul_grad", ("head",)),
        ("mul_grad", ())]
    assert all(op.scope == () for op in ops
               if op.role == framework.ROLE_OPTIMIZER)
    # the guard restores, a clone keeps, and a name is one word
    assert main._op_scope == ()
    cloned = main.clone()
    assert [op.scope for op in cloned.global_block().ops] == [
        op.scope for op in ops]
    for bad in ("", "a/b", "a b", "jvp(x)"):
        with pytest.raises(ValueError, match="one word"), \
                fluid.name_scope(bad):
            pass


def test_name_scopes_name_the_step_and_leave_the_executable_alone():
    import numpy as np

    def texts(scoped):
        main, startup, loss = _scoped_program(scoped)
        exe, scope = fluid.Executor(), Scope()
        exe.run(startup, scope=scope)
        lowered = exe._lower_step(
            main, feed={"x": np.ones((4, 8), np.float32)}, fetch_list=[loss],
            scope=scope)
        return lowered.as_text(), lowered.compile().as_text()

    (given, scoped), (given_bare, bare) = texts(True), texts(False)
    assert given == given_bare  # printed without locations
    names = _op_names(scoped)
    assert any(n.startswith("jit(step)/forward/head/bias/") for n in names)
    assert any(n.startswith("jit(step)/backward/head/") for n in names)
    assert not any("/head/" in n for n in _op_names(bare))
    # the role is still the first component, the scope the next
    assert {roles.role_of(n) for n in names if "/head/" in n} == {
        "forward", "backward"}

    def stripped(text):
        text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
        return re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                      r"\n(\d+ .*\n)*", "\n", text)

    assert stripped(scoped) == stripped(bare)
