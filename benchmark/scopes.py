"""Device time by the program's part scopes: `short_conv`, `moe_experts`, ...

Beneath the role scope of `emit_ops` (`benchmark/roles.py`), the decoder
ops lower inside `jax.named_scope` of their own part
(`paddle_tpu/ops/decoder_ops.py`, `moe_ops.py`), so an instruction's
`op_name` reads `jit(step)/forward/jvp(moe_experts)/ragged_dot_general`,
`jit(step)/backward/transpose(jvp(short_conv))/bsh,hk->bsk/dot_general` or,
recomputed, `.../backward/transpose(jvp(moe_experts))/forward/
jvp(moe_experts)/checkpoint/rematted_computation/mul`. **The part of an
instruction is the first component of its `op_name` that names one of
`PARTS`, bare or wrapped.** XLA's TPU compiler turns `ragged_dot` into
grouped-matmul custom calls of its own and names them anew
(`op_name="ragged-dot-none"`, `"ragged-dot-metadata"`): only `moe_swiglu`
makes them, so they count as `moe_experts`.

A `fusion` takes the part that all part-carrying instructions of its fused
computation share (`roles.members`); where they carry more than one it is
counted under none of them, and `python3 -m benchmark.scopes <trace dir>
[steps]` lists what was left over. The self times and the module are those
`roles` reads; over a program without part scopes (the parent of the PR
that brought them) nothing is found and nothing reported.
"""
from __future__ import annotations

import functools
import re
import statistics
from typing import Dict, FrozenSet, List, Optional, Sequence

from . import roles, trace_reduce

PARTS = ("rms_norm", "rope", "short_conv", "swiglu_ffn", "moe_route",
         "moe_dispatch", "moe_experts", "moe_combine")
RAGGED_DOT = "ragged-dot"  # XLA's own names for the grouped products
_WORD = re.compile(r"[A-Za-z0-9_]+")


def part_of(op_name: str) -> Optional[str]:
    """The part an `op_name` carries, or None."""
    if op_name.startswith(RAGGED_DOT):
        return "moe_experts"
    for component in op_name.split("/"):
        for word in _WORD.findall(component):
            if word in PARTS:
                return word
    return None


def carried_parts(module: roles.Module) -> Dict[str, FrozenSet[str]]:
    """Instruction name -> the parts it carries: its own, or for a fusion
    those of the instructions of its fused computation."""
    return {ins.name: frozenset(
                p for p in (part_of(m.op_name)
                            for m in roles.members(module, ins)) if p)
            for ins in module.instructions()}


class PartSplit:
    """Self time of a traced window by part, every device by itself."""

    def __init__(self, found: roles.RoleSplit,
                 carried: Dict[str, FrozenSet[str]]):
        self.steps = found.steps
        self.carried = carried
        self.op_names = found.op_names
        self.devices = found.devices

    def _ns(self, device, wanted) -> float:
        return sum(ns for name, ns in device.ns_by_instruction.items()
                   if wanted(self.carried.get(name, frozenset())))

    def ms_per_step(self, parts: Sequence[str]) -> float:
        """Instructions that carry exactly one part, one of `parts`;
        median over the devices."""
        return statistics.median(
            self._ns(d, lambda c: len(c) == 1 and next(iter(c)) in parts)
            for d in self.devices) * 1e-6 / self.steps


@functools.lru_cache(maxsize=1)
def split_of_trace(xplane_path: str, steps: int) -> Optional[PartSplit]:
    found = roles.split_of_trace(xplane_path, steps)
    if found is None:
        return None
    modules = roles.modules_in(xplane_path)
    module = roles.step_module(
        modules,
        trace_reduce.load_xplane(xplane_path) if len(modules) > 1 else ())
    carried = carried_parts(module)
    return PartSplit(found, carried) if any(carried.values()) else None


def part_ms_per_step(run, parts: Sequence[str]) -> Optional[float]:
    """Device milliseconds a step under `parts`, from the trace the harness
    left in its trace directory; None for an untraced run and for a
    program that has no part scopes."""
    if run.trace is None:
        return None
    from . import harness

    found = split_of_trace(trace_reduce.find_xplane(harness.TRACE_DIR),
                           run.trace.steps)
    return None if found is None else found.ms_per_step(tuple(parts))


def describe(found: PartSplit, top: int = 12) -> str:
    per_step = 1e-6 / found.steps
    rows: List[str] = []
    for d in found.devices:
        by_part = {p: 0.0 for p in PARTS}
        mixed: Dict[str, float] = {}
        for name, ns in d.ns_by_instruction.items():
            carried = found.carried.get(name, frozenset())
            if len(carried) == 1:
                by_part[next(iter(carried))] += ns
            elif carried:
                mixed[name] = ns
        rows.append(
            f"DEVICE {d.ordinal}: busy {d.busy_ns * per_step:.3f} ms a step; "
            + ", ".join(f"{p} {ns * per_step:.3f}"
                        for p, ns in by_part.items())
            + f"; mixed {sum(mixed.values()) * per_step:.3f}")
        for name in sorted(mixed, key=mixed.get, reverse=True)[:top]:
            rows.append(f"    {mixed[name] * per_step:9.4f} ms  {name}  "
                        f"{'+'.join(sorted(found.carried[name]))}  "
                        f"{found.op_names.get(name, '')}")
    return "\n".join(rows)


if __name__ == "__main__":
    import os
    import sys

    target = sys.argv[1]
    if os.path.isdir(target):
        target = trace_reduce.find_xplane(target)
    result = split_of_trace(
        target, int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    print("no instruction of the traced module carries a part scope"
          if result is None else describe(result))
