"""Device milliseconds a step in the dropless fallback of the expert
layers: the sorted block at T x k rows that a device scalar picks, through
a `conditional`, where a held expert's rows pass twice what uniform
routing gives (`paddle_tpu/ops/moe_ops.py:_held_part`). Both branches are
in the compiled step and only the one that ran is in the trace, so 0 says
that no layer of the traced steps fell back and a number says that routing
was off balance there.

Counted are the instructions of the branch computations that lower under
the scope `moe_full_width`, XLA's `ragged-dot-*` calls in them included,
and not the scope's own `op_name`s: XLA moves what both branches compute
alike out of the `conditional` and leaves it the fallback's name (9.9 ms a
step of such instructions in a window that never fell back, my chip run,
PR 34). Absent where the run is untraced or the step has no such branch."""
import statistics

from benchmark import part_scopes, roles, trace_reduce

LAYER = "experts"
MOVES = "tokens_per_s_per_chip"
UNIT = "ms"
SOURCE = "device_trace"
SCOPE = ("moe_full_width",)


def fallback_instructions(module):
    """Names of the instructions of every `conditional`'s branch
    computations that are the fallback's: most of what such a branch holds
    under any scope (XLA's `ragged-dot-*` calls carry none) lowers under
    this one."""
    names = set()
    for ins in module.instructions():
        if ins.opcode != "conditional":
            continue
        for called in ins.calls:
            body = module.computations.get(called, ())
            scoped = [bool(part_scopes.part_of(m.op_name, SCOPE))
                      for i in body for m in roles.members(module, i)
                      if "/" in m.op_name]
            if scoped and 2 * sum(scoped) > len(scoped):
                names.update(i.name for i in body)
    return names


def read(run):
    if run.trace is None:
        return None
    from benchmark import harness

    path = trace_reduce.find_xplane(harness.TRACE_DIR)
    found = roles.split_of_trace(path, run.trace.steps)
    if found is None:
        return None
    modules = roles.modules_in(path)
    module = roles.step_module(
        modules, trace_reduce.load_xplane(path) if len(modules) > 1 else ())
    names = fallback_instructions(module)
    if not names:
        return None
    return statistics.median(
        sum(ns for name, ns in d.ns_by_instruction.items() if name in names)
        for d in found.devices) * 1e-6 / found.steps
