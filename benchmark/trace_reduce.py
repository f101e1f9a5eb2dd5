"""From a profiler trace to numbers: the one reduction every PR is measured by.

Input is the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData` (nothing but JAX), or a chrome-trace JSON of the
same lines (the fixture under `fixtures/`). Both become `Plane`s of `Line`s
of `Event`s on one clock in nanoseconds.

What is read (TPU v5e, jax 0.9 / libtpu 0.0.34; looked at by hand, PERF.md
section 3 has the listing):

- a device is a plane named `/device:TPU:<n>`; its line `XLA Ops` holds one
  event per executed HLO instruction, named by the instruction
  (`fusion.12`, `while.3`, `all-reduce-start.1`), nested where an
  instruction runs others (a `while` holds its body's instructions);
- asynchronous work in flight (copies, collectives between their `-start`
  and `-done`) is on the lines whose name starts with `Async XLA Ops`;
- the host is the plane `/host:CPU`; `jax.profiler.TraceAnnotation` spans
  appear on the line of the thread that opened them, under their own name.

Nesting is kept: an event's self time is its duration less what its direct
children cover, so the twelve layers inside the scan's `while` are counted
once and under their own names, and the self times of a line sum to that
line's busy time.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
import statistics
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE_PREFIX = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")
# an instruction name as the trace shows it: `%fusion.3 = f32[..] fusion(..`
# in older traces, the bare `fusion.3` in newer ones
_INSTRUCTION = re.compile(r"^%?([A-Za-z0-9_.\-]+)")
# two events closer than this are treated as touching (timestamps are
# picoseconds rounded to a float of nanoseconds)
_EPS_NS = 1e-3

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]

    def line(self, name: str) -> Optional[Line]:
        for ln in self.lines:
            if ln.name == name:
                return ln
        return None


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------


def instruction_name(event_name: str) -> str:
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def load_xplane(path: str) -> List[Plane]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [
                Event(ev.name, float(ev.start_ns),
                      float(ev.start_ns) + float(ev.duration_ns))
                for ev in line.events
            ]
            lines.append(Line(line.name, events))
        planes.append(Plane(plane.name, lines))
    return planes


def load_chrome(path: str) -> List[Plane]:
    """A chrome-trace JSON (optionally gzipped) with `process_name` and
    `thread_name` metadata; `ts` and `dur` are microseconds."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    pnames: Dict[int, str] = {}
    tnames: Dict[Tuple[int, int], str] = {}
    rows: Dict[Tuple[int, int], List[Event]] = {}
    for ev in events:
        if ev.get("ph") == "M":
            if ev["name"] == "process_name":
                pnames[ev["pid"]] = ev["args"]["name"]
            elif ev["name"] == "thread_name":
                tnames[(ev["pid"], ev["tid"])] = ev["args"]["name"]
        elif ev.get("ph") == "X":
            start = float(ev["ts"]) * 1e3
            rows.setdefault((ev["pid"], ev["tid"]), []).append(
                Event(ev["name"], start, start + float(ev["dur"]) * 1e3))
    planes: Dict[int, Plane] = {}
    for (pid, tid), evs in sorted(rows.items()):
        pname = pnames.get(pid, str(pid))
        if pname.startswith("device: "):
            pname = pname[len("device: "):]
        plane = planes.setdefault(pid, Plane(pname, []))
        plane.lines.append(Line(tnames.get((pid, tid), str(tid)), evs))
    return list(planes.values())


def find_xplane(trace_dir: str) -> str:
    import glob
    import os

    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """a minus b; both already unions (sorted, disjoint)."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def measure(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    lo, hi = window
    out = []
    for ev in events:
        if ev.end <= lo or ev.start >= hi:
            continue
        out.append(ev if (ev.start >= lo and ev.end <= hi)
                   else Event(ev.name, max(ev.start, lo), min(ev.end, hi)))
    return out


# ---------------------------------------------------------------------------
# nesting and self time
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Nested:
    event: Event
    depth: int
    self_ns: float
    leaf: bool


def nest(events: Iterable[Event]) -> List[Nested]:
    """Events of one line with their depth and self time. An event is the
    child of the innermost earlier event that still runs when it starts."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    out: List[Nested] = []
    stack: List[Nested] = []
    for ev in ordered:
        while stack and ev.start >= stack[-1].event.end - _EPS_NS:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent.self_ns -= min(ev.end, parent.event.end) - ev.start
            parent.leaf = False
        node = Nested(ev, len(stack), ev.duration, True)
        out.append(node)
        stack.append(node)
    for node in out:
        node.self_ns = max(node.self_ns, 0.0)
    return out


# ---------------------------------------------------------------------------
# one device
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceReduction:
    ordinal: int
    window_ns: float
    busy_ns: float
    self_ns_by_name: Dict[str, float]
    calls_by_name: Dict[str, int]
    kernel_ns: Dict[str, float]  # by pallas_call name
    kernel_calls: Dict[str, List[Tuple[str, float]]]  # name -> (instr, ns)
    collective_ns: float
    collective_exposed_ns: float
    gaps: List[Interval]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns


def reduce_device(plane: Plane, window: Interval,
                  kernel_of: Callable[[str], Optional[str]] = lambda n: None,
                  label_of: Optional[Callable[[str], str]] = None,
                  ) -> Optional[DeviceReduction]:
    """Everything the layer metrics read, for one device inside `window`.
    `kernel_of` maps an HLO instruction name to the `pallas_call(name=)` of
    the Mosaic kernel it runs, or None; `label_of` names an instruction in
    the table of self times (default: the kernel, else the instruction)."""
    m = DEVICE_PLANE.match(plane.name)
    ops = plane.line(OPS_LINE)
    if m is None or ops is None:
        return None
    events = clip(ops.events, window)
    if not events:
        return None
    nodes = nest(events)
    busy = union((n.event.start, n.event.end) for n in nodes if n.depth == 0)

    self_ns: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    kernel_ns: Dict[str, float] = {}
    kernel_calls: Dict[str, List[Tuple[str, float]]] = {}
    compute_leaves: List[Interval] = []
    collectives: List[Interval] = []
    for n in nodes:
        instr = instruction_name(n.event.name)
        kernel = kernel_of(instr)
        label = label_of(instr) if label_of else (kernel or instr)
        self_ns[label] = self_ns.get(label, 0.0) + n.self_ns
        calls[label] = calls.get(label, 0) + 1
        if kernel is not None:
            kernel_ns[kernel] = kernel_ns.get(kernel, 0.0) + n.event.duration
            kernel_calls.setdefault(kernel, []).append(
                (instr, n.event.duration))
        if COLLECTIVE.match(instr):
            collectives.append((n.event.start, n.event.end))
        elif n.leaf:
            compute_leaves.append((n.event.start, n.event.end))
    for ln in plane.lines:
        if ln.name.startswith(ASYNC_LINE_PREFIX):
            collectives += [
                (ev.start, ev.end) for ev in clip(ln.events, window)
                if COLLECTIVE.match(instruction_name(ev.name))]
    coll = union(collectives)
    exposed = subtract(coll, union(compute_leaves))
    gaps = subtract([window], busy)
    return DeviceReduction(
        ordinal=int(m.group(1)),
        window_ns=window[1] - window[0],
        busy_ns=measure(busy),
        self_ns_by_name=self_ns,
        calls_by_name=calls,
        kernel_ns=kernel_ns,
        kernel_calls=kernel_calls,
        collective_ns=measure(coll),
        collective_exposed_ns=measure(exposed),
        gaps=gaps,
    )


# ---------------------------------------------------------------------------
# the host's spans, and the whole trace
# ---------------------------------------------------------------------------


def host_spans(planes: Sequence[Plane], prefix: str) -> List[Event]:
    """`TraceAnnotation` spans whose name starts with `prefix`."""
    out: List[Event] = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for ln in plane.lines:
            out += [ev for ev in ln.events if ev.name.startswith(prefix)]
    return sorted(out, key=lambda e: e.start)


def self_segments(events: Iterable[Event]) -> List[Tuple[float, float, str]]:
    """The timeline of innermost events: (start, end, name) pieces, sorted
    and disjoint, each the part of an event that no child of it covers."""
    out: List[Tuple[float, float, str]] = []
    stack: List[list] = []  # [event, cursor]

    def close(entry):
        ev, cursor = entry
        if ev.end > cursor:
            out.append((cursor, ev.end, ev.name))

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and ev.start >= stack[-1][0].end - _EPS_NS:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            if ev.start > parent[1]:
                out.append((parent[1], ev.start, parent[0].name))
            parent[1] = max(parent[1], min(ev.end, parent[0].end))
        stack.append([ev, ev.start])
    while stack:
        close(stack.pop())
    out.sort()
    return out


def attribute_gaps(gaps: Sequence[Interval], spans: Sequence[Event],
                   ) -> Dict[str, float]:
    """Seconds of device idle time by what the host was doing: each gap is
    shared among the innermost spans that overlap it, and what no span
    covers goes to `(no span)`."""
    out: Dict[str, float] = {}
    segments = self_segments(spans)
    j = 0
    for lo, hi in gaps:
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        covered = 0.0
        k = j
        while k < len(segments) and segments[k][0] < hi:
            s_lo, s_hi, name = segments[k]
            got = min(hi, s_hi) - max(lo, s_lo)
            if got > 0:
                out[name] = out.get(name, 0.0) + got * 1e-9
                covered += got
            k += 1
        if hi - lo > covered:
            out["(no span)"] = (out.get("(no span)", 0.0)
                                + (hi - lo - covered) * 1e-9)
    return out


@dataclasses.dataclass
class TraceReduction:
    devices: List[DeviceReduction]
    window_s: float
    steps: int
    idle_by_span_s: Dict[str, float]

    def median(self, f: Callable[[DeviceReduction], float]) -> float:
        return statistics.median(f(d) for d in self.devices)

    @property
    def busy_s(self) -> float:
        """Mean over the devices used, as the contract's `device.busy_s`."""
        return statistics.fmean(d.busy_ns for d in self.devices) * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """[[name, seconds], ...] by self time, median across devices."""
        names = set().union(*(d.self_ns_by_name for d in self.devices))
        rows = [
            [name, self.median(lambda d: d.self_ns_by_name.get(name, 0.0))
             * 1e-9]
            for name in names
        ]
        rows.sort(key=lambda r: -r[1])
        return rows[:n]

    def top_gaps(self, n: int = 10) -> List[List]:
        rows = [[k, v] for k, v in self.idle_by_span_s.items()]
        rows.sort(key=lambda r: -r[1])
        return rows[:n]


WINDOW_SPAN = "bench.window"


def reduce_trace(planes: Sequence[Plane], steps: int,
                 kernel_of: Callable[[str], Optional[str]] = lambda n: None,
                 label_of: Optional[Callable[[str], str]] = None,
                 window: Optional[Interval] = None,
                 span_prefix: str = "bench.") -> Optional[TraceReduction]:
    """The traced window of a run. The window is the `bench.window` span
    the loop opens around its traced groups; every device is reduced by
    itself. Returns None when the trace holds no device plane with
    operations in the window: a reader that finds nothing returns nothing."""
    spans = host_spans(planes, span_prefix)
    if window is None:
        marks = [s for s in spans if s.name == WINDOW_SPAN]
        if not marks:
            return None
        window = (marks[0].start, marks[-1].end)
    devices = [d for d in (reduce_device(p, window, kernel_of, label_of)
                           for p in planes) if d is not None]
    if not devices:
        return None
    devices.sort(key=lambda d: d.ordinal)
    inner = [s for s in spans if s.name != WINDOW_SPAN]
    # gaps are attributed on the first device: one process drives them all
    idle = attribute_gaps(devices[0].gaps, clip(inner, window))
    return TraceReduction(devices, (window[1] - window[0]) * 1e-9, steps,
                          idle)


# ---------------------------------------------------------------------------
# looking at a trace by hand
# ---------------------------------------------------------------------------


def describe(planes: Sequence[Plane], per_line: int = 6) -> str:
    rows = []
    for plane in planes:
        rows.append(f"PLANE {plane.name!r}")
        for ln in plane.lines:
            evs = ln.events
            if not evs:
                rows.append(f"  LINE {ln.name!r}: empty")
                continue
            lo = min(e.start for e in evs)
            hi = max(e.end for e in evs)
            rows.append(f"  LINE {ln.name!r}: {len(evs)} events, "
                        f"{lo:.0f}..{hi:.0f} ns")
            longest = sorted(evs, key=lambda e: -e.duration)[:per_line]
            for e in longest:
                rows.append(f"      {e.duration * 1e-6:10.3f} ms  "
                            f"@{e.start:.0f}  {e.name[:100]}")
    return "\n".join(rows)


if __name__ == "__main__":
    import sys

    path = sys.argv[1]
    loader = load_xplane if path.endswith(".pb") else load_chrome
    print(describe(loader(path)))
