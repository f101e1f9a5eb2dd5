"""Device time by role: forward, backward, optimizer.

The program lowers every op under `jax.named_scope(<role>)`
(`paddle_tpu/ops/registry.py:emit_ops`), so an HLO instruction's `op_name`
reads `jit(step)/backward/transpose(jvp())/while/body/.../dot_general`. **The
role of an instruction is the first component of its `op_name` that is not
a `jit(...)` wrapper**, if that is one of the three; recomputation under
`jax.checkpoint` is emitted at the grad op and so counts as backward. A
`fusion` takes the role that all role-carrying instructions of its fused
computation share; where they carry more than one its role is `mixed`,
whatever XLA named the fusion after. No `op_name`, or no role in it: `none`.

The instructions come from the trace itself: the profiler writes the
`HloProto` of every module that ran into the plane `/host:metadata` of the
`.xplane.pb` (one event-metadata entry per module, named like the events of
the device's `XLA Modules` line, `jit_step(<id>)`, with the proto as a bytes
stat). `jax.profiler.ProfileData` shows lines and events only and that
plane has none, so the file's protobuf wire format is read here, as far as
needed: planes, their event metadata, and of the module its computations
and each instruction's name, opcode, `op_name` and called computations.
So the roles are those of the executable the device ran in the traced
window, and a run of a program without role scopes finds none and reports
nothing.

Self time by instruction is `trace_reduce.reduce_trace` with the
instruction's own name as its label; on each device the five roles' self
times sum to its busy time exactly, as the self times do.

    python3 -m benchmark.roles <trace dir or .xplane.pb> [steps]

prints the split a step, the `mixed` and `none` instructions behind it and
the collectives by role.
"""
from __future__ import annotations

import dataclasses
import functools
import statistics
from typing import Dict, FrozenSet, Iterator, List, Optional, Sequence, Tuple

from . import harness, trace_reduce

ROLES = ("forward", "backward", "optimizer")
MIXED = "mixed"
NONE = "none"
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"


def role_of(op_name: str) -> Optional[str]:
    """The role an `op_name` carries, or None."""
    for part in op_name.split("/"):
        if part.startswith("jit(") or not part:
            continue
        return part if part in ROLES else None
    return None


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Instruction:
    name: str
    opcode: str
    op_name: str = ""
    calls: Tuple[int, ...] = ()  # ids of the computations it calls


@dataclasses.dataclass
class Module:
    """An HLO module as far as the roles need it: computations by id."""
    name: str
    computations: Dict[int, List[Instruction]]

    def instructions(self) -> Iterator[Instruction]:
        for instructions in self.computations.values():
            yield from instructions


def members(module: Module, ins: Instruction) -> Iterator[Instruction]:
    """The instruction itself, or for a fusion the instructions of its
    fused computation (the TPU compiler nests fusions: those of an inner
    fusion's too)."""
    if ins.opcode != "fusion":
        yield ins
        return
    for c in ins.calls:
        for inner in module.computations.get(c, ()):
            yield from members(module, inner)


def carried_roles(module: Module) -> Dict[str, FrozenSet[str]]:
    """Instruction name -> the roles it carries: its own, or for a fusion
    those of the instructions of its fused computation."""
    return {ins.name: frozenset(
                r for r in (role_of(m.op_name) for m in members(module, ins))
                if r)
            for ins in module.instructions()}


def one_role(carried: FrozenSet[str]) -> str:
    """`forward`, `backward`, `optimizer`, `mixed` or `none`."""
    return (next(iter(carried)) if len(carried) == 1
            else MIXED if carried else NONE)


def instruction_roles(module: Module) -> Dict[str, str]:
    return {name: one_role(carried)
            for name, carried in carried_roles(module).items()}


# ---------------------------------------------------------------------------
# protobuf wire format, as far as the trace file and the module need it
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _ints(value) -> List[int]:
    """A repeated int64 field's entry: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def read_module(proto) -> Module:
    """`HloModuleProto` (xla/service/hlo.proto): name 1, computations 3;
    a computation's instructions 2 and id 5; an instruction's name 1,
    opcode 2, metadata 7 (whose op_name is 2), called_computation_ids 38."""
    name, computations = "", {}
    for number, value in _fields(memoryview(proto)):
        if number == 1:
            name = _text(value)
        elif number == 3:
            cid, instructions = 0, []
            for n, v in _fields(value):
                if n == 5:
                    cid = v
                elif n == 2:
                    iname = opcode = op_name = ""
                    calls: List[int] = []
                    for k, w in _fields(v):
                        if k == 1:
                            iname = _text(w)
                        elif k == 2:
                            opcode = _text(w)
                        elif k == 7:
                            op_name = next(
                                (_text(x) for j, x in _fields(w) if j == 2),
                                "")
                        elif k == 38:
                            calls += _ints(w)
                    instructions.append(
                        Instruction(iname, opcode, op_name, tuple(calls)))
            computations[cid] = instructions
    return Module(name, computations)


def modules_in(xplane_path: str) -> Dict[str, Module]:
    """Every module the trace file carries, by the name its runs have on
    the `XLA Modules` line. `XSpace`: planes 1; `XPlane`: name 2,
    event_metadata 4 (a map: value 2); `XEventMetadata`: name 2, stats 5;
    `XStat`: bytes_value 6, which holds an `HloProto` (hlo_module 1)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Module] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        fields = list(_fields(plane))
        if not any(n == 2 and _text(v) == METADATA_PLANE for n, v in fields):
            continue
        for n, entry in fields:
            if n != 4:
                continue
            metadata = next((v for k, v in _fields(entry) if k == 2), None)
            if metadata is None:
                continue
            name, proto = "", None
            for k, v in _fields(metadata):
                if k == 2:
                    name = _text(v)
                elif k == 5:
                    blob = next((x for j, x in _fields(v) if j == 6), None)
                    if blob is not None:
                        proto = next(
                            (x for j, x in _fields(blob) if j == 1), None)
            if proto is not None:
                out[name] = read_module(proto)
    return out


def step_module(modules: Dict[str, Module],
                planes: Sequence[trace_reduce.Plane]) -> Optional[Module]:
    """The module that ran longest on the devices' `XLA Modules` lines
    (the step); where the trace carries one module only, that one."""
    if len(modules) == 1:
        return next(iter(modules.values()))
    seconds: Dict[str, float] = {}
    for plane in planes:
        line = (plane.line(MODULES_LINE)
                if trace_reduce.DEVICE_PLANE.match(plane.name) else None)
        for ev in (line.events if line else ()):
            seconds[ev.name] = seconds.get(ev.name, 0.0) + ev.duration
    ran = [n for n in sorted(seconds, key=seconds.get, reverse=True)
           if n in modules]
    return modules[ran[0]] if ran else None


# ---------------------------------------------------------------------------
# the split
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceRoles:
    ordinal: int
    busy_ns: float
    ns_by_role: Dict[str, float]  # the three, `mixed` and `none`
    ns_by_instruction: Dict[str, float]

    @property
    def unattributed_ns(self) -> float:
        """Busy time less the three roles: `mixed` plus `none`."""
        return self.busy_ns - sum(self.ns_by_role[r] for r in ROLES)


@dataclasses.dataclass
class RoleSplit:
    devices: List[DeviceRoles]
    steps: int
    roles: Dict[str, str]  # instruction -> role
    carried: Dict[str, FrozenSet[str]]
    op_names: Dict[str, str]

    def ms_per_step(self, role: str) -> float:
        """Median over the devices, as the other device-trace metrics."""
        return statistics.median(
            d.ns_by_role[role] for d in self.devices) * 1e-6 / self.steps

    def unattributed_ms_per_step(self) -> float:
        return statistics.median(
            d.unattributed_ns for d in self.devices) * 1e-6 / self.steps


def split(planes: Sequence[trace_reduce.Plane], module: Module, steps: int,
          ) -> Optional[RoleSplit]:
    """Self time of the traced window by role, every device by itself.
    None where the trace holds no device operations in the window, or the
    module no instruction with a role (a program without role scopes)."""
    carried = carried_roles(module)
    if not any(carried.values()):
        return None
    reduction = trace_reduce.reduce_trace(
        planes, steps, label_of=lambda instruction: instruction)
    if reduction is None:
        return None
    roles = {name: one_role(c) for name, c in carried.items()}
    devices = []
    for d in reduction.devices:
        by_role = dict.fromkeys(ROLES + (MIXED, NONE), 0.0)
        for instruction, ns in d.self_ns_by_name.items():
            by_role[roles.get(instruction, NONE)] += ns
        devices.append(DeviceRoles(d.ordinal, d.busy_ns, by_role,
                                   d.self_ns_by_name))
    return RoleSplit(devices, steps, roles, carried,
                     {i.name: i.op_name for i in module.instructions()})


@functools.lru_cache(maxsize=1)
def split_of_trace(xplane_path: str, steps: int) -> Optional[RoleSplit]:
    planes = trace_reduce.load_xplane(xplane_path)
    module = step_module(modules_in(xplane_path), planes)
    return None if module is None else split(planes, module, steps)


def split_of(run) -> Optional[RoleSplit]:
    """The split of a run's traced window, from the trace file the harness
    left in its trace directory; None for an untraced run."""
    if run.trace is None:
        return None
    return split_of_trace(trace_reduce.find_xplane(harness.TRACE_DIR),
                          run.trace.steps)


def role_ms_per_step(run, role: str) -> Optional[float]:
    found = split_of(run)
    return None if found is None else found.ms_per_step(role)


def unattributed_ms_per_step(run) -> Optional[float]:
    found = split_of(run)
    return None if found is None else found.unattributed_ms_per_step()


# ---------------------------------------------------------------------------
# looking at a split by hand
# ---------------------------------------------------------------------------


def describe(found: RoleSplit, top: int = 12) -> str:
    rows = []
    for d in found.devices:
        per_step = 1e-6 / found.steps
        rows.append(
            f"DEVICE {d.ordinal}: busy {d.busy_ns * per_step:.3f} ms a step; "
            + ", ".join(f"{r} {d.ns_by_role[r] * per_step:.3f}"
                        for r in ROLES + (MIXED, NONE)))
        for role in (MIXED, NONE):
            held = sorted(
                ((ns, i) for i, ns in d.ns_by_instruction.items()
                 if found.roles.get(i, NONE) == role), reverse=True)
            rows.append(f"  {role}: {len(held)} instructions, "
                        f"{sum(ns for ns, _ in held) * per_step:.3f} ms; "
                        f"the longest:")
            if role == MIXED:
                pairs: Dict[str, float] = {}
                for ns, i in held:
                    what = "+".join(sorted(found.carried[i]))
                    pairs[what] = pairs.get(what, 0.0) + ns
                rows.append("    " + ", ".join(
                    f"{what} {ns * per_step:.3f}"
                    for what, ns in sorted(pairs.items())))
            for ns, i in held[:top]:
                what = ("+".join(sorted(found.carried.get(i, ())))
                        if role == MIXED else "")
                rows.append(f"    {ns * per_step:9.4f} ms  {i}  {what}  "
                            f"{found.op_names.get(i, '(not in the module)')}")
        by_role: Dict[str, List[Tuple[float, str]]] = {}
        for i, ns in d.ns_by_instruction.items():
            if trace_reduce.COLLECTIVE.match(i):
                by_role.setdefault(found.roles.get(i, NONE), []).append(
                    (ns, i))
        for role, held in sorted(by_role.items()):
            rows.append(
                f"  collectives under {role}: "
                f"{sum(ns for ns, _ in held) * per_step:.4f} ms self time; "
                + ", ".join(f"{i} {ns * per_step:.4f}"
                            for ns, i in sorted(held, reverse=True)[:top]))
    return "\n".join(rows)


if __name__ == "__main__":
    import os
    import sys

    target = sys.argv[1]
    if os.path.isdir(target):
        target = trace_reduce.find_xplane(target)
    result = split_of_trace(
        target, int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    print("no instruction of the traced module carries a role"
          if result is None else describe(result))
