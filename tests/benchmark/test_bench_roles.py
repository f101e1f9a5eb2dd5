"""Device time by role on the CPU: the rule, the split of a hand-made trace
against hand-computed values, the trace file's wire format, the four
readers, and what a run without a device or without role scopes reports."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import manifest, roles
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane

READERS = {name: manifest.load_module("layer_metrics", name) for name in (
    "forward_ms_per_step", "backward_ms_per_step", "optimizer_ms_per_step",
    "role_unattributed_ms_per_step")}
FWD = "jit(step)/forward/jvp()/while/body/"
BWD = "jit(step)/backward/transpose(jvp())/while/body/"
MS = 1e6  # ns


def test_the_role_is_the_first_component_after_the_jit_wrappers():
    assert roles.role_of(FWD + "closed_call/dot_general") == "forward"
    assert roles.role_of(
        BWD + "closed_call/checkpoint/rematted_computation/dot_general"
    ) == "backward"  # recomputation is emitted at the grad op
    assert roles.role_of("jit(step)/jit(main)/optimizer/sub") == "optimizer"
    # a sub-block's ops nest a second role: the outermost counts
    assert roles.role_of("jit(step)/backward/while/body/forward/add") == (
        "backward")
    # the executor's RNG advance, an argument, a reducer's body, nothing
    assert roles.role_of("jit(step)/jit(_threefry_fold_in)/add") is None
    assert roles.role_of("donated_vals['fc_0.w_0']") is None
    assert roles.role_of("reduce_sum") is None
    assert roles.role_of("jit(step)/forwards/add") is None
    assert roles.role_of("") is None


def _module():
    """An entry computation whose `while` holds a forward fusion, a backward
    fusion, a fusion XLA named after the update whose body also holds the
    weight gradient, an instruction without `op_name`, and a Mosaic call
    reached through a fusion."""
    ins = roles.Instruction
    return roles.Module("jit_step", {
        1: [ins("while.1", "while", "jit(step)/forward/jvp()/while", (2, 3)),
            ins("all-reduce.1", "all-reduce",
                "jit(step)/backward/transpose(jvp())/dot_general"),
            ins("fusion.9", "fusion", "jit(step)/optimizer/sub", (7,)),
            ins("copy-done.1", "copy-done"),
            ins("fold.1", "fusion", "jit(step)/jit(_threefry_fold_in)/add",
                (9,))],
        2: [ins("fusion.1", "fusion", FWD + "dot_general", (4,)),
            ins("fusion.2", "fusion", BWD + "dot_general", (5,)),
            ins("fusion.3", "fusion", "jit(step)/optimizer/sub", (6,)),
            ins("copy.1", "copy"),
            ins("fusion.4", "fusion", BWD + "dynamic_update_slice", (8,))],
        3: [ins("compare.1", "compare", "jit(step)/forward/jvp()/while/cond/lt")],
        4: [ins("param_0.1", "parameter"),
            ins("dot.1", "dot", FWD + "dot_general"),
            ins("add.1", "add", FWD + "add")],
        5: [ins("dot.2", "dot", BWD + "dot_general"),
            # the TPU compiler nests fusions; the inner one's name says
            # nothing, its body does
            ins("fusion.5", "fusion", "", (10,))],
        6: [ins("convolution.1", "convolution", BWD + "conv_general_dilated"),
            ins("constant.6", "constant"),
            ins("sub.1", "subtract", "jit(step)/optimizer/sub")],
        7: [ins("sub.2", "subtract", "jit(step)/optimizer/sub"),
            ins("param_0.7", "parameter", "donated_vals['w']")],
        8: [ins("custom-call.1", "custom-call",
                BWD + "closed_call/flash_bsh_bwd/pallas_call"),
            ins("dus.1", "dynamic-update-slice", BWD + "dynamic_update_slice")],
        9: [ins("add.9", "add", "jit(step)/jit(_threefry_fold_in)/add")],
        10: [ins("mul.2", "multiply", BWD + "closed_call/checkpoint/"
                 "rematted_computation/mul")],
    })


def _device(ordinal, mixed_end):
    """One pass over the module in 120 ms: the `while` 0..100 with 5 ms of
    its own, then the all-reduce, the update, a copy, the RNG advance, and
    8 ms of nothing."""
    return Plane(f"/device:TPU:{ordinal}", [
        Line(tr.OPS_LINE, [
            Event("%while.1 = (s32[]{:T(128)}, bf16[64,512,768]) while(",
                  0 * MS, 100 * MS),
            Event("%fusion.1 = bf16[64,512,768]{2,1,0} fusion(", 0 * MS,
                  30 * MS),
            Event("fusion.2", 30 * MS, 60 * MS),
            Event("fusion.3", 60 * MS, mixed_end * MS),
            Event("copy.1", mixed_end * MS, 75 * MS),
            Event("fusion.4", 75 * MS, 95 * MS),
            Event("all-reduce.1", 100 * MS, 104 * MS),
            Event("fusion.9", 104 * MS, 110 * MS),
            Event("copy-done.1", 110 * MS, 111 * MS),
            Event("fold.1", 111 * MS, 112 * MS),
        ]),
        Line(roles.MODULES_LINE, [Event("jit_step(7)", 0 * MS, 112 * MS)]),
    ])


def _planes():
    host = Plane(tr.HOST_PLANE, [Line("python3", [
        Event(tr.WINDOW_SPAN, 0 * MS, 120 * MS)])])
    return [_device(0, 70), _device(1, 72), host]


def test_a_fusion_takes_the_role_its_body_shares():
    module = _module()
    assert roles.instruction_roles(module) == {
        "while.1": "forward", "compare.1": "forward", "fusion.1": "forward",
        "dot.1": "forward", "add.1": "forward",
        "fusion.2": "backward", "dot.2": "backward", "fusion.5": "backward",
        "mul.2": "backward",
        "all-reduce.1": "backward", "convolution.1": "backward",
        # the Mosaic call through the fusion that holds it
        "fusion.4": "backward", "custom-call.1": "backward",
        "dus.1": "backward",
        # named after the update, and its body holds the weight gradient
        "fusion.3": "mixed",
        "fusion.9": "optimizer", "sub.1": "optimizer", "sub.2": "optimizer",
        # no op_name, or none with a role in it
        "copy.1": "none", "copy-done.1": "none", "param_0.1": "none",
        "constant.6": "none", "param_0.7": "none",
        "fold.1": "none", "add.9": "none",
    }
    assert roles.carried_roles(module)["fusion.3"] == {"backward",
                                                       "optimizer"}


def test_the_split_against_hand_values_and_the_busy_time():
    found = roles.split(_planes(), _module(), steps=2)
    d0, d1 = found.devices
    assert d0.busy_ns == pytest.approx(112 * MS)
    # forward: fusion.1 and the `while`'s own 5 ms; backward: fusion.2,
    # the Mosaic fusion, the all-reduce; none: two copies and the RNG
    assert d0.ns_by_role == pytest.approx({
        "forward": 35 * MS, "backward": 54 * MS, "optimizer": 6 * MS,
        "mixed": 10 * MS, "none": 7 * MS})
    assert d1.ns_by_role == pytest.approx({
        "forward": 35 * MS, "backward": 54 * MS, "optimizer": 6 * MS,
        "mixed": 12 * MS, "none": 5 * MS})
    for d in found.devices:
        assert sum(d.ns_by_role.values()) == pytest.approx(d.busy_ns,
                                                           rel=1e-12)
        assert d.unattributed_ns == pytest.approx(
            d.ns_by_role["mixed"] + d.ns_by_role["none"])
    assert found.ms_per_step("forward") == pytest.approx(17.5)
    assert found.ms_per_step("backward") == pytest.approx(27.0)
    assert found.ms_per_step("optimizer") == pytest.approx(3.0)
    assert found.unattributed_ms_per_step() == pytest.approx(8.5)
    listing = roles.describe(found)
    assert "fusion.3  backward+optimizer  jit(step)/optimizer/sub" in listing
    assert "collectives under backward: 2.0000 ms" in listing


def test_without_role_scopes_or_without_a_device_there_is_no_split():
    bare = roles.Module("jit_step", {
        1: [roles.Instruction(i.name, i.opcode,
                              i.op_name.replace("forward/", "")
                              .replace("backward/", "")
                              .replace("optimizer/", ""), i.calls)
            for c in _module().computations.values() for i in c]})
    assert roles.split(_planes(), bare, steps=2) is None  # the parent
    assert roles.split(_planes()[2:], _module(), steps=2) is None  # a CPU


# ---------------------------------------------------------------------------
# the trace file
# ---------------------------------------------------------------------------


def _varint(value):
    out = bytearray()
    while True:
        byte, value = value & 0x7F, value >> 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _module_proto(module):
    out = _field(1, module.name)
    for cid, instructions in module.computations.items():
        comp = _field(1, f"computation.{cid}") + _field(5, cid)
        for n, i in enumerate(instructions):
            calls = b"".join(_varint(c) for c in i.calls)
            comp += _field(2, (
                _field(1, i.name) + _field(2, i.opcode)
                + _field(3, b"\x08\x0b")  # a shape, skipped
                + (_field(7, _field(1, "op") + _field(2, i.op_name))
                   if i.op_name else b"")
                + _field(35, n)
                # packed, as proto3 writes it, and one by one
                + (_field(38, calls) if len(i.calls) != 1
                   else _field(38, i.calls[0]))))
        out += _field(3, comp)
    return out


def _xplane(planes, modules):
    """An `XSpace` with the planes' lines and events, and the modules'
    `HloProto`s in the metadata plane, as the profiler writes them."""
    space = b""
    for plane in planes:
        names = sorted({ev.name for ln in plane.lines for ev in ln.events})
        ids = {name: n + 1 for n, name in enumerate(names)}
        body = _field(2, plane.name)
        for n, ln in enumerate(plane.lines):
            line = _field(1, n + 1) + _field(2, ln.name)
            for ev in ln.events:
                line += _field(4, _field(1, ids[ev.name])
                               + _field(2, int(ev.start * 1e3))
                               + _field(3, int(ev.duration * 1e3)))
            body += _field(3, line)
        for name, n in ids.items():
            body += _field(4, _field(1, n)
                           + _field(2, _field(1, n) + _field(2, name)))
        space += _field(1, body)
    body = _field(2, roles.METADATA_PLANE)
    body += _field(5, _field(1, 1) + _field(2, _field(1, 1)
                                            + _field(2, "Hlo Proto")))
    for n, (name, module) in enumerate(modules.items()):
        stat = _field(1, 1) + _field(6, _field(1, _module_proto(module))
                                     + _field(3, b"\x0a\x00"))
        body += _field(4, _field(1, n + 1) + _field(2, (
            _field(1, n + 1) + _field(2, name) + _field(5, stat))))
    return space + _field(1, body)


@pytest.fixture()
def trace_dir(tmp_path):
    startup = roles.Module("jit_step", {1: [roles.Instruction(
        "fusion.1", "fusion", "jit(step)/forward/broadcast_in_dim")]})
    path = tmp_path / "plugins" / "profile" / "2026_01_01" / "hand.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_xplane(_planes(), {"jit_step(3)": startup,
                                         "jit_step(7)": _module()}))
    return str(tmp_path)


def test_the_module_is_read_from_the_trace_file(trace_dir):
    path = tr.find_xplane(trace_dir)
    modules = roles.modules_in(path)
    assert sorted(modules) == ["jit_step(3)", "jit_step(7)"]
    assert modules["jit_step(7)"] == _module()
    # the one whose runs fill the devices' `XLA Modules` lines
    planes = tr.load_xplane(path)
    assert roles.step_module(modules, planes) == _module()
    assert roles.step_module({"only": _module()}, []) == _module()
    assert roles.step_module(modules, planes[2:]) is None
    found = roles.split_of_trace(path, 2)
    assert found.ms_per_step("backward") == pytest.approx(27.0)
    assert found.unattributed_ms_per_step() == pytest.approx(8.5)


def test_the_four_readers(trace_dir, monkeypatch):
    from benchmark import harness

    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    run = types.SimpleNamespace(trace=types.SimpleNamespace(steps=2))
    got = {name: reader.read(run) for name, reader in READERS.items()}
    assert got == pytest.approx({
        "forward_ms_per_step": 17.5, "backward_ms_per_step": 27.0,
        "optimizer_ms_per_step": 3.0, "role_unattributed_ms_per_step": 8.5})
    # on each device the four sum to its busy time a step; here the two
    # devices are busy alike, so the medians do too
    assert sum(got.values()) == pytest.approx(112 / 2)
    untraced = types.SimpleNamespace(trace=None)
    assert [r.read(untraced) for r in READERS.values()] == [None] * 4
    doc = manifest.load_manifest()
    rows = {m["name"]: m for m in doc["per_layer"]}
    for name, reader in READERS.items():
        assert "workloads" not in rows[name]  # every cell
        assert (reader.LAYER, reader.MOVES, reader.UNIT, reader.SOURCE) == (
            rows[name]["layer"], "step_ms", "ms", "device_trace")
    assert [rows[n]["layer"] for n in READERS] == ["entry"] * 3 + ["device"]


def test_a_traced_step_on_the_cpu_carries_its_module(tmp_path):
    """What the profiler itself writes: the metadata plane of a trace of a
    jitted step holds the module with the scopes' names, and a CPU has no
    device plane, so there is no split."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("forward"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("optimizer"):
            return x - 0.1 * y

    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            step(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    module = next(m for name, m in roles.modules_in(path).items()
                  if name.startswith("jit_step("))
    found = set(roles.instruction_roles(module).values())
    assert {"forward", "optimizer"} <= found <= {
        "forward", "optimizer", "mixed", "none"}
    assert roles.split_of_trace(path, 1) is None


def test_rehearsal_runs_the_readers_and_leaves_device_metrics_out(tmp_path):
    """`--rehearse --trace 1` finds the four readers by name and runs them
    on the CPU's trace; with no device plane there they report nothing, as
    the other device-trace metrics, and the run is `correct`. Run from a
    copy of the benchmark, whose trace directory no other test shares."""
    root = str(tmp_path)
    shutil.copytree(manifest.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "resnet50.train224", "--rehearse", "--seed", "5",
         "--seconds", "1", "--trace", "1"],
        cwd=root, text=True, capture_output=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=manifest.ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    # the run left a trace whose module carries the roles
    path = tr.find_xplane(os.path.join(root, ".bench_trace"))
    found = [set(roles.instruction_roles(m).values())
             for m in roles.modules_in(path).values()]
    assert any(set(roles.ROLES) <= f for f in found)
    cell = manifest.load_cell(manifest.load_manifest(), "resnet50.train224",
                              rehearse=True)
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    assert "program_build_s" in result["metrics"]
    assert not set(READERS) & set(result["metrics"])
    assert "device_idle_share" not in result["metrics"]
