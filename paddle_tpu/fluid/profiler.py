"""Profiler: host event spans + device (XLA) trace -> chrome timeline.

Parity surface: reference platform/profiler.h:126 (RecordEvent),
EnableProfiler/DisableProfiler (:208,211), device_tracer.cc:61 (CUPTI
capture), python profiler.py:131,198,255 (start_profiler, stop_profiler,
profiler context manager) and tools/timeline.py (chrome trace export).

TPU-native design: RecordEvent is the one host span of the framework (the
executor and the loader time every phase through it, and nothing else);
device-side timing comes from the JAX / XLA profiler (xplane), the TPU
analog of CUPTI. A RecordEvent is a `jax.profiler.TraceAnnotation` first,
so under any profiler session the host spans lie in the xplane's
`/host:CPU` plane on the clock of the device lines. stop_profiler writes
ONE chrome-trace JSON (host pid 0, device pid 1+ — open in
chrome://tracing or Perfetto): from the xplane where a device trace ran,
from the recorded host spans alone under state "CPU"; it prints the
reference-style summary table and leaves the raw xplane file beside the
JSON for xprof/tensorboard.
"""
from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..telemetry import tracing as _tracing

_lock = threading.Lock()
_enabled = False
_events: List[tuple] = []  # (name, tid, start_ns, end_ns)
_trace_dir: Optional[str] = None
_device_tracing = False


def is_profiler_enabled() -> bool:
    return _enabled


class RecordEvent:
    """RAII host span (reference platform/profiler.h:126), a context
    manager. On entry it opens a `jax.profiler.TraceAnnotation(name)`,
    always: 0.3 us with no profiler session running, and under one the
    span lies in the xplane beside the device's operations. The same
    object feeds, each only while its consumer is armed:

    - `_events`, the list behind the summary table and the chrome trace,
      under start_profiler;
    - `into`, the consumer of the span's milliseconds that the caller
      hands in where one is armed: a record whose field `key` the span
      adds to (the monitor's StepRecord), or, with no `key`, a histogram
      that observes it;
    - a span in telemetry.tracing's ring under PADDLE_TRACING, a child of
      the thread's innermost one, with `attrs`.

    With `step_num` the span is the root of a step: a
    `StepTraceAnnotation`, so that xprof groups the spans of one step
    under its number, and the ring's `step` root that RPCs and the step
    record join on. `ms` holds the duration after exit."""

    __slots__ = ("name", "ms", "_into", "_key", "_attrs", "_step_num",
                 "_note", "_scope", "_start")

    def __init__(self, name: str, into=None, key: Optional[str] = None,
                 attrs: Optional[dict] = None,
                 step_num: Optional[int] = None):
        self.name = name
        self.ms = 0.0
        self._into = into
        self._key = key
        self._attrs = attrs
        self._step_num = step_num

    def __enter__(self):
        step = self._step_num is not None
        self._note = (StepTraceAnnotation(self.name, step_num=self._step_num)
                      if step else TraceAnnotation(self.name))
        self._note.__enter__()
        self._scope = None
        if _tracing.enabled():
            self._scope = (_tracing.step_span(self._attrs) if step
                           else _tracing.span(self.name, attrs=self._attrs))
            self._scope.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, etype, evalue, tb):
        end = time.perf_counter_ns()
        self.ms = (end - self._start) / 1e6
        if self._into is not None:
            if self._key is None:
                self._into.observe(self.ms)
            else:
                setattr(self._into, self._key,
                        getattr(self._into, self._key) + self.ms)
        if _enabled:
            with _lock:
                _events.append((self.name, threading.get_ident(),
                                self._start, end))
        if self._scope is not None:
            self._scope.__exit__(etype, evalue, tb)
        self._note.__exit__(etype, evalue, tb)
        return False


def reset_profiler():
    """reference profiler.py reset_profiler."""
    with _lock:
        _events.clear()


def start_profiler(state: str = "All", tracer_option: str = "Default"):
    """state: CPU (host spans only) | GPU/All (also start the XLA device
    trace — 'GPU' kept for API parity, it means 'device')."""
    global _enabled, _trace_dir, _device_tracing
    if _enabled:
        return
    reset_profiler()
    _enabled = True
    _trace_dir = None
    if state in ("GPU", "All"):
        import jax

        _trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_prof_")
        try:
            jax.profiler.start_trace(_trace_dir)
            _device_tracing = True
        except Exception:  # noqa: BLE001 — device tracing is best-effort
            _device_tracing = False


def stop_profiler(sorted_key: Optional[str] = "total",
                  profile_path: str = "/tmp/profile"):
    """Stop, print the summary table, write `<profile_path>.json` (chrome
    trace) and leave the xplane dir (device) beside it."""
    global _enabled, _device_tracing, _trace_dir
    if not _enabled:
        return
    _enabled = False
    if _device_tracing:
        import jax

        jax.profiler.stop_trace()
        _device_tracing = False

    events = list(_events)
    _print_summary(events, sorted_key)
    # where a device trace ran, the xplane holds both tracks on one clock:
    # every RecordEvent is a TraceAnnotation in its host plane, beside the
    # device's operations. Only without one (state "CPU", or an xplane
    # that cannot be read) is the recorded list the trace's only track.
    chrome = _xplane_chrome_events(_trace_dir) if _trace_dir else []
    if not chrome:
        chrome = _host_chrome_events(events)
    out = profile_path if profile_path.endswith(".json") else profile_path + ".json"
    d = os.path.dirname(out)
    if d:  # dirless paths write to the cwd — nothing to create
        os.makedirs(d, exist_ok=True)
    with open(out, "w") as f:
        json.dump({"traceEvents": chrome, "displayTimeUnit": "ms"}, f)
    if _trace_dir:
        print(f"[profiler] chrome trace: {out}; raw xplane: {_trace_dir}")
    else:
        print(f"[profiler] chrome trace: {out}")
    _trace_dir = None


def export_chrome_trace(path: str) -> str:
    """SNAPSHOT the host spans recorded so far into a chrome-trace JSON
    WITHOUT stopping the profiler (events keep accumulating; device
    xplane events only appear in stop_profiler's trace — the device
    trace cannot be read mid-flight). The launcher's per-rank timeline
    collection (PADDLE_TRACE_DIR) uses exactly this. Returns the path
    written."""
    with _lock:
        events = list(_events)
    out = path if path.endswith(".json") else path + ".json"
    d = os.path.dirname(out)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{out}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"traceEvents": _host_chrome_events(events),
                   "displayTimeUnit": "ms"}, f)
    os.replace(tmp, out)  # the launcher may merge while we run
    return out


_collection_started = False


def maybe_start_trace_collection() -> bool:
    """Launcher contract (launch.py --trace_dir): when PADDLE_TRACE_DIR
    is set, record host spans for the life of the process and dump
    `<dir>/trace.<rank>.json` at exit; the launcher merges the per-rank
    files into one timeline (telemetry.timeline). Called by
    parallel.env.init_parallel_env — launched trainers opt in without
    code changes. No-op (False) when the env var is unset."""
    global _collection_started, _enabled
    directory = os.environ.get("PADDLE_TRACE_DIR")
    if not directory or _collection_started:
        return _collection_started
    _collection_started = True
    _enabled = True  # host spans only; device tracing stays user-driven
    rank = os.environ.get("PADDLE_TRAINER_ID", "0")
    path = os.path.join(directory, f"trace.{rank}.json")

    import atexit

    atexit.register(lambda: export_chrome_trace(path))
    return True


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key: Optional[str] = "total",
             profile_path: str = "/tmp/profile", tracer_option: str = "Default"):
    """reference profiler.py:255 context manager."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# ---------------------------------------------------------------------------
# summary + chrome trace assembly
# ---------------------------------------------------------------------------


def _print_summary(events, sorted_key):
    agg: Dict[str, List[float]] = {}
    for name, _tid, s, e in events:
        agg.setdefault(name, []).append((e - s) / 1e6)
    rows = []
    for name, durs in agg.items():
        rows.append((name, len(durs), sum(durs), sum(durs) / len(durs),
                     min(durs), max(durs)))
    key_idx = {"calls": 1, "total": 2, "ave": 3, "min": 4, "max": 5}.get(
        sorted_key or "total", 2
    )
    rows.sort(key=lambda r: r[key_idx], reverse=True)
    if not rows:
        return
    print(f"{'Event':<44}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
          f"{'Min(ms)':>10}{'Max(ms)':>10}")
    for r in rows:
        print(f"{r[0][:43]:<44}{r[1]:>8}{r[2]:>12.3f}{r[3]:>10.3f}"
              f"{r[4]:>10.3f}{r[5]:>10.3f}")


def _host_chrome_events(events):
    if not events:
        return []
    t0 = min(s for _, _, s, _ in events)
    out = [{"name": "process_name", "ph": "M", "pid": 0,
            "args": {"name": "host (python)"}}]
    for name, tid, s, e in events:
        out.append({
            "name": name, "ph": "X", "pid": 0, "tid": tid % 10_000,
            "ts": (s - t0) / 1e3, "dur": (e - s) / 1e3,
        })
    return out


def load_xplane(trace_dir) -> Optional[Any]:
    """Locate and parse the newest .xplane.pb under `trace_dir` into an
    XSpace proto. Best-effort, but never SILENT: when the device track
    is unavailable the reason is logged once, so a host-only trace (or
    an empty cost report) is explainable instead of mysterious. Returns
    None when the file or the schema is missing."""
    import sys
    import glob

    if not trace_dir:
        return None
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True),
                   key=os.path.getmtime)
    if not files:
        print(f"[profiler] device track skipped: no .xplane.pb under "
              f"{trace_dir} (device tracing produced no output)",
              file=sys.stderr)
        return None
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:  # noqa: BLE001 — schema unavailable: skip merge
        print(f"[profiler] device track skipped: xplane schema "
              f"unavailable ({type(e).__name__}: {e}); raw xplane kept "
              f"at {trace_dir} for xprof/tensorboard", file=sys.stderr)
        return None
    xs = xplane_pb2.XSpace()
    try:
        with open(files[-1], "rb") as f:
            xs.ParseFromString(f.read())
    except Exception as e:  # noqa: BLE001 — torn/foreign xplane file
        print(f"[profiler] device track skipped: failed to parse "
              f"{files[-1]} ({type(e).__name__}: {e})", file=sys.stderr)
        return None
    return xs


def xplane_op_events(source) -> Dict[str, Dict[str, Any]]:
    """Aggregate XLA op executions out of an xplane trace: HLO
    instruction name -> {dur_ps, count, flops, bytes_accessed,
    hlo_module}. `source` is a trace dir or an already-parsed XSpace.

    An event counts as an op execution when it carries an `hlo_op` stat
    (the CPU thunk executor and the GPU/TPU device planes both stamp
    one) or lives on an "XLA Ops" device line (TPU op track). Everything
    else — thunk scheduling, host python, allocator spans — is runtime
    overhead, not op time, and is excluded from both the numerator and
    the denominator of telemetry.cost's attribution coverage. Where the
    backend reports per-op flop counts / bytes accessed (TPU op
    profile), they ride along; the CPU backend reports none.

    Control-flow op events NEST: a `while` instruction's span contains
    its body's op executions, which the trace records as their own
    events — counting both would double-charge every scanned layer. Op
    events fully contained in an earlier-starting op event of the same
    plane are dropped: the outer instruction (which carries the op scope
    of the Program op that emitted the loop) is charged its whole span."""
    xs = load_xplane(source) if isinstance(source, str) else source
    out: Dict[str, Dict[str, Any]] = {}
    if xs is None:
        return out
    for plane in xs.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        candidates = []  # (start_ps, end_ps, name, stats)
        for line in plane.lines:
            line_is_op_track = "xla op" in (line.name or "").lower()
            base_ps = int(line.timestamp_ns) * 1000
            for ev in line.events:
                stats = {}
                for st in ev.stats:
                    sn = stat_names.get(st.metadata_id)
                    if sn:
                        stats[sn] = (st.str_value or st.int64_value
                                     or st.uint64_value or st.double_value
                                     or st.ref_value)
                if "hlo_op" not in stats and not line_is_op_track:
                    continue
                meta = plane.event_metadata[ev.metadata_id]
                name = meta.name or str(ev.metadata_id)
                start = base_ps + int(ev.offset_ps)
                candidates.append(
                    (start, start + int(ev.duration_ps), name, stats))
        # drop op events nested inside another op event (strict interval
        # containment): sort by (start, -end) so an outer span precedes
        # its children; `actives` holds kept spans still open
        candidates.sort(key=lambda c: (c[0], -c[1]))
        actives: List[Tuple[int, int]] = []
        for start, end, name, stats in candidates:
            actives = [a for a in actives if a[1] > start]
            if any(a[0] <= start and end <= a[1] for a in actives):
                continue
            actives.append((start, end))
            row = out.setdefault(name, {
                "dur_ps": 0, "count": 0, "flops": 0.0,
                "bytes_accessed": 0, "hlo_module": None,
            })
            row["dur_ps"] += end - start
            row["count"] += 1
            for key in ("flops", "bytes_accessed"):
                v = stats.get(key)
                if isinstance(v, (int, float)) and v:
                    row[key] += v
            mod = stats.get("hlo_module")
            if isinstance(mod, str) and mod:
                row["hlo_module"] = mod
    return out


def _xplane_chrome_events(trace_dir):
    """The xplane as chrome events on one time base: the host plane (the
    RecordEvents among its threads' lines) as pid 0, every device plane
    as pid 1+."""
    xs = load_xplane(trace_dir)
    if xs is None:
        return []
    out = []
    raw = []
    pid = 1
    for plane in xs.planes:
        if "TPU" not in plane.name and "CPU" not in plane.name.upper():
            continue
        host = plane.name == "/host:CPU"
        p_ = 0 if host else pid
        out.append({"name": "process_name", "ph": "M", "pid": p_,
                    "args": {"name": "host (python)" if host
                             else f"device: {plane.name}"}})
        for li, line in enumerate(plane.lines):
            out.append({"name": "thread_name", "ph": "M", "pid": p_,
                        "tid": li, "args": {"name": line.name or f"line{li}"}})
            for ev in line.events:
                meta = plane.event_metadata[ev.metadata_id]
                start_ns = line.timestamp_ns + ev.offset_ps / 1e3
                raw.append((meta.name[:120], p_, li, start_ns,
                            ev.duration_ps / 1e6))
        if not host:
            pid += 1
    if not raw:
        return []
    t0 = min(r[3] for r in raw)
    for name, p_, tid, start_ns, dur in raw:
        out.append({"name": name, "ph": "X", "pid": p_, "tid": tid,
                    "ts": (start_ns - t0) / 1e3, "dur": dur})
    return out
