"""Host time by the program's own spans, and the device's idle time under them.

The program times every phase of `Executor.run` and of its loader through one
span, `paddle_tpu/fluid/profiler.py:RecordEvent`, which is a
`jax.profiler.TraceAnnotation` first: in the harness's traced window the
spans lie in the `/host:CPU` plane of the `.xplane.pb`, on the line of the
thread that opened them and on the clock of the device's lines. Read here:

- **the loop's line**: the line of the host plane that holds the
  `bench.window` span. The loader's producer thread has a line of its own
  (`DataLoader::produce`), whose spans nest with nothing of the loop and are
  left out: what the producer costs shows only where the loop waits for it;
- **self time** of every `bench.*`, `Executor::*` and `DataLoader::*` span of
  that line inside the window (`trace_reduce.nest`): a span's duration less
  what its children cover, so the names add up to the time the line spent
  under any span, and `Executor::run`'s own is the Python between its phases;
- **idle by span**: the gaps of the first device's ops line inside the window
  (`trace_reduce.reduce_device`), each shared among the innermost spans that
  overlap it (`trace_reduce.attribute_gaps`), so that what the ledger's
  `idle_gaps` gives `bench.run_call` falls to what the program was doing.

A program without these spans (every commit before the one that added them)
leaves only the harness's three `bench.*` names; a CPU has no device plane
and so no gaps. The readers then report nothing.

    python3 -m benchmark.host_spans <trace dir or .xplane.pb> [steps]

lists both, a step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

from . import harness, trace_reduce

BENCH = "bench."
EXECUTOR = "Executor::"
LOADER = "DataLoader::"
PREFIXES = (BENCH, EXECUTOR, LOADER)
FEED = EXECUTOR + "feed"
DISPATCH = EXECUTOR + "dispatch"
RUN_CALL = BENCH + "run_call"
LONGEST = 8  # gaps the listing shows one by one


@dataclasses.dataclass
class HostSplit:
    """Of one traced window: nanoseconds of self time and calls by span
    name on the loop's line, seconds of the first device's idle time by
    innermost span (None without a device)."""
    steps: int
    window_ns: float
    self_ns: Dict[str, float]
    calls: Dict[str, int]
    duration_ns: Dict[str, float]
    idle_s: Optional[Dict[str, float]]
    # the first device's longest gaps: (ns from the window's start, ns,
    # the spans it lay under with each one's ns), longest first
    longest_gaps: List[Tuple[float, float, Dict[str, float]]]

    def named(self, prefixes: Sequence[str], but: Sequence[str] = (),
              ) -> List[str]:
        """The window's span names that start with one of `prefixes`,
        those in `but` left out."""
        return [n for n in self.self_ns
                if n.startswith(tuple(prefixes)) and n not in but]

    def self_ms_per_step(self, *prefixes: str,
                         but: Sequence[str] = ()) -> Optional[float]:
        """Self time a step of the spans `named(prefixes, but)`; None where
        the window holds no such span."""
        names = self.named(prefixes, but)
        if not names:
            return None
        return sum(self.self_ns[n] for n in names) * 1e-6 / self.steps

    def idle_ms_per_step(self, *prefixes: str,
                         but: Sequence[str] = ()) -> Optional[float]:
        """Device idle time a step under the spans `named(prefixes, but)`;
        None without a device, or where the window holds no such span (0
        where it does and the device never idled under one)."""
        names = self.named(prefixes, but)
        if self.idle_s is None or not names:
            return None
        return sum(self.idle_s.get(n, 0.0) for n in names) * 1e3 / self.steps


def loop_line(planes: Sequence[trace_reduce.Plane],
              ) -> Optional[Tuple[trace_reduce.Line, trace_reduce.Interval]]:
    """The host plane's line that holds `bench.window`, and the window."""
    for plane in planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for ln in plane.lines:
            marks = [e for e in ln.events
                     if e.name == trace_reduce.WINDOW_SPAN]
            if marks:
                return ln, (min(e.start for e in marks),
                            max(e.end for e in marks))
    return None


def split(planes: Sequence[trace_reduce.Plane], steps: int,
          ) -> Optional[HostSplit]:
    found = loop_line(planes)
    if found is None:
        return None
    line, window = found
    spans = trace_reduce.clip(
        [e for e in line.events if e.name.startswith(PREFIXES)
         and e.name != trace_reduce.WINDOW_SPAN], window)
    self_ns: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    duration_ns: Dict[str, float] = {}
    for node in trace_reduce.nest(spans):
        name = node.event.name
        self_ns[name] = self_ns.get(name, 0.0) + node.self_ns
        calls[name] = calls.get(name, 0) + 1
        duration_ns[name] = duration_ns.get(name, 0.0) + node.event.duration
    devices = [d for d in (trace_reduce.reduce_device(p, window)
                           for p in planes) if d is not None]
    idle, longest = None, []
    if devices:
        # one process drives every device: the first one's gaps, as the
        # harness's own `idle_gaps`
        first = min(devices, key=lambda d: d.ordinal)
        idle = trace_reduce.attribute_gaps(first.gaps, spans)
        for lo, hi in sorted(first.gaps, key=lambda g: g[0] - g[1])[:LONGEST]:
            under = trace_reduce.attribute_gaps([(lo, hi)], spans)
            longest.append((lo - window[0], hi - lo,
                            {n: sec * 1e9 for n, sec in under.items()}))
    return HostSplit(steps, window[1] - window[0], self_ns, calls,
                     duration_ns, idle, longest)


@functools.lru_cache(maxsize=1)
def split_of_trace(xplane_path: str, steps: int) -> Optional[HostSplit]:
    return split(trace_reduce.load_xplane(xplane_path), steps)


def split_of(run) -> Optional[HostSplit]:
    """The split of a run's traced window, from the trace file the harness
    left in its trace directory; None for an untraced run, and for one
    whose trace holds no device (a rehearsal): a time read on a CPU is no
    device number."""
    if run.trace is None:
        return None
    try:
        path = trace_reduce.find_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    return split_of_trace(path, run.trace.steps)


def self_ms_per_step(run, *prefixes: str, but: Sequence[str] = (),
                     ) -> Optional[float]:
    found = split_of(run)
    return None if found is None else found.self_ms_per_step(
        *prefixes, but=but)


def idle_ms_per_step(run, *prefixes: str, but: Sequence[str] = (),
                     ) -> Optional[float]:
    found = split_of(run)
    return None if found is None else found.idle_ms_per_step(
        *prefixes, but=but)


def program_counter_seconds(*names: str) -> Optional[float]:
    """The sum of the program's counters of these names, as the process's
    registry holds them now; None where it holds none of them (a program
    that does not count them)."""
    from paddle_tpu.telemetry import get_registry

    held = get_registry().snapshot()
    found = [held[n]["series"][0]["value"] for n in names if n in held]
    return float(sum(found)) if found else None


# ---------------------------------------------------------------------------
# looking at a split by hand
# ---------------------------------------------------------------------------


def describe(found: HostSplit) -> str:
    per_step = 1e-6 / found.steps
    rows = [f"WINDOW {found.window_ns * 1e-6:.3f} ms, {found.steps} steps, "
            f"{found.window_ns * per_step:.3f} ms a step; the loop's line:"]
    rows.append(f"  {'span':<28}{'calls':>7}{'self ms/step':>14}"
                f"{'total ms/step':>15}{'idle ms/step':>14}")
    idle = found.idle_s or {}
    for name in sorted(found.self_ns, key=lambda n: -found.self_ns[n]):
        rows.append(
            f"  {name:<28}{found.calls[name]:>7}"
            f"{found.self_ns[name] * per_step:>14.4f}"
            f"{found.duration_ns[name] * per_step:>15.4f}"
            + (f"{idle.get(name, 0.0) * 1e3 / found.steps:>14.4f}"
               if found.idle_s is not None else f"{'-':>14}"))
    if found.idle_s is None:
        rows.append("  no device plane with operations in the window: no idle")
    else:
        rest = {n: s for n, s in idle.items() if n not in found.self_ns}
        for name, s in sorted(rest.items(), key=lambda r: -r[1]):
            rows.append(f"  {name:<28}{'':>7}{'':>14}{'':>15}"
                        f"{s * 1e3 / found.steps:>14.4f}")
        rows.append(f"  device idle, all: "
                    f"{sum(idle.values()) * 1e3 / found.steps:.4f} ms a step; "
                    f"the longest gaps:")
        for at, ns, under in found.longest_gaps:
            rows.append(
                f"    {ns * 1e-6:9.4f} ms at {at * 1e-6:10.3f} ms under "
                + ", ".join(f"{n} {v * 1e-6:.4f}" for n, v in sorted(
                    under.items(), key=lambda r: -r[1])))
    call = found.duration_ns.get(RUN_CALL)
    if call and found.named([EXECUTOR]):
        inside = sum(found.self_ns[n] for n in found.named([EXECUTOR]))
        rows.append(f"  Executor::* self times {inside * per_step:.4f} ms a "
                    f"step of bench.run_call's {call * per_step:.4f}")
    return "\n".join(rows)


if __name__ == "__main__":
    import os
    import sys

    target = sys.argv[1]
    if os.path.isdir(target):
        target = trace_reduce.find_xplane(target)
    result = split_of_trace(
        target, int(sys.argv[2]) if len(sys.argv) > 2 else 1)
    print("the trace holds no bench.window span" if result is None
          else describe(result))
