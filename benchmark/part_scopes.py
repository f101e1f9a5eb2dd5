"""Device time by part scope, for any list of part names.

`scopes.py` splits a traced step by the eight part names PR 28 brought and
holds them as a constant; a configuration that lowers under further scopes
(`mla`, `mhc_map`, `mhc_mix`, `shared_expert`: `paddle_tpu/ops/
latent_ops.py`, `decoder_ops.py`) needs the same split over its own names.
This file is that split with the names as an argument, over `scopes.py`'s
and `roles.py`'s own pieces (the module of the trace, the members of a
fusion, the self times, `PartSplit`), which it leaves as they are.

**The part of an instruction is the first component of its `op_name` that
names one of `parts`, bare or wrapped** (`jvp(mla)`,
`transpose(jvp(mhc_mix))/checkpoint/..`): a norm's multiply inside `mla`
reads `.../jvp(mla)/mul` and is `mla`'s; the kernel `mla` calls reads
`.../jvp(mla)/flash_mla_causal_fwd/pallas_call`, whose first component that
is a part name is again `mla` (`flash_mla_causal_fwd` is one word and no
part). XLA's own `ragged-dot-*` names count as `moe_experts` where that is
among `parts`. A fusion that mixes parts is counted under none. Over a
program without the scopes nothing is found and nothing reported.
"""
from __future__ import annotations

import functools
from typing import Dict, FrozenSet, Optional, Sequence, Tuple

from . import roles, scopes, trace_reduce

# the eight accepted names and what the latent-attention, hyper-connection
# and shared-expert ops add
PARTS = scopes.PARTS + ("mla", "mhc_map", "mhc_mix", "shared_expert")


def part_of(op_name: str, parts: Sequence[str] = PARTS) -> Optional[str]:
    """The part an `op_name` carries among `parts`, or None."""
    if op_name.startswith(scopes.RAGGED_DOT):
        return "moe_experts" if "moe_experts" in parts else None
    for component in op_name.split("/"):
        for word in scopes._WORD.findall(component):
            if word in parts:
                return word
    return None


def carried_parts(module: roles.Module, parts: Sequence[str] = PARTS
                  ) -> Dict[str, FrozenSet[str]]:
    """Instruction name -> the parts it carries: its own, or for a fusion
    those of the instructions of its fused computation."""
    return {ins.name: frozenset(
                p for p in (part_of(m.op_name, parts)
                            for m in roles.members(module, ins)) if p)
            for ins in module.instructions()}


@functools.lru_cache(maxsize=2)
def split_of_trace(xplane_path: str, steps: int,
                   parts: Tuple[str, ...] = PARTS
                   ) -> Optional[scopes.PartSplit]:
    found = roles.split_of_trace(xplane_path, steps)
    if found is None:
        return None
    modules = roles.modules_in(xplane_path)
    module = roles.step_module(
        modules,
        trace_reduce.load_xplane(xplane_path) if len(modules) > 1 else ())
    carried = carried_parts(module, parts)
    return scopes.PartSplit(found, carried) if any(carried.values()) else None


def part_ms_per_step(run, wanted: Sequence[str],
                     parts: Tuple[str, ...] = PARTS) -> Optional[float]:
    """Device milliseconds a step under the parts `wanted`, split among
    `parts`, from the trace the harness left in its trace directory; None
    for an untraced run, for a program that has no part scopes, and where
    no instruction carries one of `wanted` (the parent of the PR that
    brought them)."""
    if run.trace is None:
        return None
    from . import harness

    found = split_of_trace(trace_reduce.find_xplane(harness.TRACE_DIR),
                           run.trace.steps, tuple(parts))
    if found is None or not any(
            set(c) & set(wanted) for c in found.carried.values()):
        return None
    return found.ms_per_step(tuple(wanted))


def describe(found: scopes.PartSplit, parts: Sequence[str] = PARTS,
             top: int = 6) -> str:
    """Every part's device time a step and its longest instructions, and
    the fusions that mix parts."""
    per_step = 1e-6 / found.steps
    rows = []
    for d in found.devices:
        by_part: Dict[str, Dict[str, float]] = {p: {} for p in parts}
        mixed: Dict[str, float] = {}
        for name, ns in d.ns_by_instruction.items():
            carried = found.carried.get(name, frozenset())
            if len(carried) == 1:
                by_part[next(iter(carried))][name] = ns
            elif carried:
                mixed[name] = ns
        rows.append(f"DEVICE {d.ordinal}: busy {d.busy_ns * per_step:.3f} ms "
                    f"a step; mixed {sum(mixed.values()) * per_step:.3f}")
        for part, times in by_part.items():
            if not times:
                continue
            rows.append(f"  {part} {sum(times.values()) * per_step:.3f} ms, "
                        f"{len(times)} instructions")
            for name in sorted(times, key=times.get, reverse=True)[:top]:
                rows.append(f"    {times[name] * per_step:9.4f} ms  {name}  "
                            f"{found.op_names.get(name, '')}")
        for name in sorted(mixed, key=mixed.get, reverse=True)[:top]:
            rows.append(f"  mixed {mixed[name] * per_step:9.4f} ms  {name}  "
                        f"{'+'.join(sorted(found.carried[name]))}")
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
