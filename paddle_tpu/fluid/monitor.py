"""Executor step-time breakdown on the telemetry layer (ISSUE 4).

Answers "where did this step go" for the whole-block-XLA execution
model, where op boundaries vanish inside one compiled program
(arXiv:2301.13062) and the only honest per-phase account is at the
executor's seams:

  data_wait_ms   host time spent materializing the feed (plus, in
                 dataset loops, the time blocked on the input iterator
                 — timed_iter / add_data_wait)
  compile_ms     trace + XLA compile when the step misses the cache;
                 cache_hit / retraces count the misses that matter
                 (a RETRACE is a new compile for a program the cache
                 already held under a different signature — the silent
                 shape-instability tax)
  device_ms      the compiled step call. Honest only under the
                 FLAGS_benchmark fence (block_until_ready inside the
                 timed window); without the fence it measures dispatch,
                 which is what the async hot path actually pays
                 (span Executor::dispatch; Executor::first_dispatch, the
                 call that traces, lowers and compiles, is compile_ms)
  fetch_ms       device->host conversion of the fetch list
  ckpt_save_ms   CheckpointManager.save durations (attached to the next
                 committed step record)
  idle_ms        raw gap between the previous Executor.run return and
                 this one's entry — the goodput ledger's idle signal
                 (ISSUE 15). Iterator wait recorded by timed_iter in
                 that gap also lands in data_wait_ms; the ledger
                 classifies by residual so nothing double-counts
  peak_hbm_bytes device allocator high-water (jax memory_stats), the
                 MAX across all local devices — per-device values land
                 in the device_peak_hbm_bytes{device=...} gauges and
                 debugz /memz; 0 where the backend reports none (CPU)

Cost contract: with PADDLE_METRICS_PATH unset nothing here touches the
filesystem or fences the device; the always-on residue is a handful of
counter increments and one deque append per step (the step-rate sample
the straggler heartbeat rides on), unmeasurable next to any real step.

The four phase fields are filled by the executor's spans
(profiler.RecordEvent, handed the record), and by nothing else.

What a compile is made of, counted where JAX itself measures it
(`jax.monitoring`), and only while this thread is inside one of the
executor's compile spans (CompileEvent: Executor::compile,
Executor::first_dispatch, Executor::aot), so that no other jit of the
process counts:

  executor_trace_seconds_total            Program -> jaxpr (emit_ops)
  executor_lower_seconds_total            jaxpr -> StableHLO module
  executor_backend_compile_seconds_total  XLA's compile, or the read of
                                          the executable from JAX's
                                          persistent cache
  executor_persistent_cache_hits_total    compile requests that cache
                                          served

Every number also lands in the process metrics registry
(telemetry.get_registry()) for the Prometheus exposition.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Optional, Tuple

from ..telemetry import get_registry, goodput, sink
from .profiler import RecordEvent

_reg = get_registry()

# always-on counters, resolved per use (get-or-create) so a registry
# reset() in tests never leaves orphaned metric objects behind


def _counter(name, help=""):
    return _reg.counter(name, help=help)

_lock = threading.Lock()
_tls = threading.local()

# step-rate sample for the heartbeat/straggler channel: recent commit
# timestamps (monotonic) -> avg step seconds over the window
_recent = collections.deque(maxlen=16)
_step_count = 0
_pending_data_wait_ms = 0.0
_pending_ckpt_save_ms = 0.0
_hb_registered = False

# recent step records for the debugz /steps endpoint (same dicts the
# JSONL sink writes); populated only while a consumer exists (sink on
# or debugz armed) — the flag-off hot path builds no dicts
_recent_steps = collections.deque(maxlen=128)
_keep_recent = False
_aux_armed = False

# idle accounting (ISSUE 15): perf_counter at the end of the previous
# Executor.run — the gap to the next begin_step is the step record's
# idle_ms, the goodput ledger's idle signal
_last_run_end: Optional[float] = None
# rolling (data_wait_ms, wall_ms) per recent step: the data-starved
# fraction heartbeat stamps carry for input-skew attribution
_dw_window = collections.deque(maxlen=16)
_last_commit_wall: Optional[float] = None


def enabled() -> bool:
    """True when per-step records are being written (PADDLE_METRICS_PATH
    set or telemetry.sink.enable() called)."""
    return sink.enabled()


def _arm_aux() -> None:
    """One-shot arming of the env-gated telemetry consumers that ride
    the step loop: the debugz introspection server (PADDLE_DEBUGZ_PORT —
    arming it also turns on the /steps ring buffer) and the metrics push
    exporter (PADDLE_METRICS_PUSH_URL). Cost after the first call: one
    bool read."""
    global _aux_armed, _keep_recent
    if _aux_armed:
        return
    _aux_armed = True
    try:
        from ..telemetry import debugz, export

        if debugz.maybe_serve() is not None:
            _keep_recent = True
        export.maybe_start()
        export.maybe_start_traces()
    except Exception:  # noqa: BLE001 — introspection never fails a step
        pass
    try:
        from ..telemetry import tracing

        if tracing.enabled():
            # tracing rides the step loop too: keep the /steps ring (the
            # flight recorder dumps it next to the span ring) and arm
            # the SIGTERM/crash/exit dump hooks
            _keep_recent = True
            tracing.maybe_install_hooks()
    except Exception:  # noqa: BLE001
        pass


def recent_steps() -> list:
    """Most-recent step records, oldest first (debugz /steps)."""
    with _lock:
        return list(_recent_steps)


class StepRecord:
    __slots__ = ("data_wait_ms", "compile_ms", "device_ms", "fetch_ms",
                 "ckpt_save_ms", "idle_ms", "cache_hit", "fenced")

    def __init__(self):
        self.data_wait_ms = 0.0
        self.compile_ms = 0.0
        self.device_ms = 0.0
        self.fetch_ms = 0.0
        self.ckpt_save_ms = 0.0
        self.idle_ms = 0.0
        self.cache_hit = True
        self.fenced = False


def begin_step() -> Optional[StepRecord]:
    """Open a step record when a consumer exists (JSONL sink on, the
    debugz server armed — its /steps page reads the same records — or
    the goodput ledger classifying wall-clock); None otherwise. The
    record is thread-local so _ensure_compiled (called deeper in the
    stack) can contribute compile numbers."""
    _arm_aux()
    if not (sink.enabled() or _keep_recent or goodput.enabled()):
        return None
    rec = StepRecord()
    if _last_run_end is not None:
        # raw gap between consecutive Executor.run calls. Iterator wait
        # (timed_iter) happens inside this gap and ALSO lands in
        # data_wait_ms — the goodput ledger classifies by residual, so
        # a dataset loop's idle is the gap net of its data wait
        rec.idle_ms = max(
            0.0, (time.perf_counter() - _last_run_end) * 1e3)
    _tls.rec = rec
    return rec


def current_record() -> Optional[StepRecord]:
    return getattr(_tls, "rec", None)


def abandon_step() -> None:
    """Drop the open record (step raised; nothing committed)."""
    global _last_run_end
    _tls.rec = None
    _last_run_end = time.perf_counter()


def record_compile(ms: float, retrace: bool) -> None:
    """Called by Executor._ensure_compiled on a cache MISS."""
    _counter("executor_cache_misses_total",
             "compile-cache misses (first compiles)").inc()
    if retrace:
        _counter("executor_retraces_total",
                 "recompiles of an already-compiled program under a new "
                 "feed signature / flag set (shape instability)").inc()
    _reg.histogram("executor_compile_ms",
                   help="trace+XLA compile durations").observe(ms)
    rec = current_record()
    if rec is not None:
        rec.compile_ms += ms
        rec.cache_hit = False


# jax.monitoring's name -> the counter and its help. A duration event is
# announced by a scalar of the same name when it starts, which is how the
# outermost of nested ones is told: a kernel's inner jit is traced inside
# the step's trace and reports a duration of its own within it.
_COMPILE_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": (
        "executor_trace_seconds_total",
        "seconds tracing the executor's programs into jaxprs"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "executor_lower_seconds_total",
        "seconds lowering the executor's jaxprs to StableHLO"),
    "/jax/core/compile/backend_compile_duration": (
        "executor_backend_compile_seconds_total",
        "seconds in XLA's compile, or the persistent cache's read, of "
        "the executor's programs"),
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_listening = False


def _compile_depths() -> Optional[dict]:
    """This thread's open duration events by name, or None outside the
    executor's compile spans."""
    return getattr(_tls, "compile_depths", None)


def _on_compile_scalar(name, value, **kw):
    depths = _compile_depths()
    if depths is not None and name in _COMPILE_SECONDS:
        depths[name] = depths.get(name, 0) + 1


def _on_compile_duration(name, secs, **kw):
    depths = _compile_depths()
    if depths is None or name not in _COMPILE_SECONDS:
        return
    depths[name] = max(depths.get(name, 1) - 1, 0)
    if depths[name] == 0:
        _counter(*_COMPILE_SECONDS[name]).inc(secs)


def _on_compile_event(name, **kw):
    if name == _CACHE_HIT_EVENT and _compile_depths() is not None:
        _counter("executor_persistent_cache_hits_total",
                 "compile requests of the executor's programs served by "
                 "JAX's persistent compilation cache").inc()


class CompileEvent(RecordEvent):
    """The RecordEvent of a phase that holds the program's own compiles
    (Executor::compile, Executor::first_dispatch, Executor::aot): while
    one is open in this thread, what `jax.monitoring` reports is added
    to the executor_* compile counters. The listeners are registered
    once, by the first of these spans; outside one they return after a
    thread-local read."""

    __slots__ = ("_outermost",)

    def __enter__(self):
        global _listening
        if not _listening:
            import jax

            _listening = True
            jax.monitoring.register_scalar_listener(_on_compile_scalar)
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_duration)
            jax.monitoring.register_event_listener(_on_compile_event)
        self._outermost = _compile_depths() is None
        if self._outermost:
            _tls.compile_depths = {}
        return super().__enter__()

    def __exit__(self, etype, evalue, tb):
        if self._outermost:
            _tls.compile_depths = None
        return super().__exit__(etype, evalue, tb)


def record_cache_hit() -> None:
    _counter("executor_cache_hits_total", "compile-cache hits").inc()


def add_data_wait(ms: float) -> None:
    """Input-pipeline wait attributed to the NEXT step (dataset loops
    block on the iterator BEFORE calling run)."""
    global _pending_data_wait_ms
    with _lock:
        _pending_data_wait_ms += ms


def observe_checkpoint_save(ms: float) -> None:
    global _pending_ckpt_save_ms
    _reg.histogram("checkpoint_save_ms",
                   help="CheckpointManager.save durations").observe(ms)
    with _lock:
        _pending_ckpt_save_ms += ms


def timed_iter(iterable):
    """Wrap a batch iterator so time blocked on next() lands in the
    following step's data_wait_ms. Pass-through when telemetry is off."""
    if not sink.enabled():
        yield from iterable
        return
    it = iter(iterable)
    while True:
        t0 = time.perf_counter()
        try:
            v = next(it)
        except StopIteration:
            return
        add_data_wait((time.perf_counter() - t0) * 1e3)
        yield v


def device_memory_stats() -> list:
    """Per-LOCAL-device allocator stats: one dict per device with the
    high-water mark, current usage and the allocator limit where the
    backend reports them (TPU; CPU reports nothing and yields zeros).
    The multi-chip truth behind peak_hbm_bytes — a mesh spanning >1
    local chip has one high-water PER DEVICE, and "does it fit" is a
    per-device question (debugz /memz serves this list live)."""
    out = []
    try:
        import jax

        for i, d in enumerate(jax.local_devices()):
            try:
                stats = d.memory_stats() or {}
            except Exception:  # noqa: BLE001 — backend may not report
                stats = {}
            out.append({
                "device": i,
                "kind": getattr(d, "device_kind", "?"),
                "peak_bytes": int(stats.get("peak_bytes_in_use")
                                  or stats.get("bytes_in_use") or 0),
                "bytes_in_use": int(stats.get("bytes_in_use") or 0),
                "bytes_limit": int(stats.get("bytes_limit") or 0),
            })
    except Exception:  # noqa: BLE001 — diagnostics never fail the step
        pass
    return out


def peak_hbm_bytes() -> int:
    """Device allocator high-water mark — the MAX across all local
    devices (jax memory_stats). The old scalar name and schema are kept
    for compatibility; before ISSUE 11 this read local_devices()[0]
    only, which under-reported the moment a mesh spanned >1 chip
    (device 0 is not necessarily the fullest). 0 when the backend
    reports nothing (CPU). Per-device values: device_memory_stats()
    and the device_peak_hbm_bytes{device=...} gauges."""
    stats = device_memory_stats()
    return max((d["peak_bytes"] for d in stats), default=0)


def mark_step() -> int:
    """Always-on per-step bookkeeping: step counter + the step-rate
    sample the heartbeat stamps carry. Returns the step index just
    completed (0-based monotone per process)."""
    global _step_count, _hb_registered
    _counter("executor_steps_total", "Executor.run completions").inc()
    with _lock:
        step = _step_count
        _step_count += 1
        _recent.append(time.monotonic())
    if not _hb_registered:
        _hb_registered = True
        try:  # publish (step, avg step time) through the heartbeat file
            from ..distributed import heartbeat

            heartbeat.set_step_provider(step_rate_sample)
            heartbeat.set_aux_provider(
                lambda: {"data_frac": data_wait_fraction()})
        except Exception:  # noqa: BLE001 — liveness channel is optional
            pass
    return step


def global_step() -> int:
    return _step_count


def step_rate_sample() -> Tuple[int, Optional[float]]:
    """(steps completed, recent avg step seconds or None) — the payload
    heartbeat stamps carry for launcher-side straggler detection."""
    with _lock:
        n = _step_count
        if len(_recent) >= 2:
            span = _recent[-1] - _recent[0]
            avg = span / (len(_recent) - 1) if span > 0 else None
        else:
            avg = None
    return n, avg


def data_wait_fraction() -> Optional[float]:
    """Recent input-pipeline share of step wall time (0..1), or None
    when no telemetry consumer is armed / no window yet. Rides the
    heartbeat stamps (input-skew attribution: a straggler whose
    data_frac is high is data-starved, not compute-slow)."""
    if not (sink.enabled() or goodput.enabled() or _keep_recent):
        return None
    with _lock:
        dw = sum(d for d, _ in _dw_window)
        wall = sum(w for _, w in _dw_window)
    if wall <= 0:
        return None
    return round(min(1.0, dw / wall), 4)


def commit_step(rec: Optional[StepRecord]) -> None:
    """Close the step: always-on bookkeeping, plus the JSONL record and
    gauges when telemetry output is on."""
    global _pending_data_wait_ms, _pending_ckpt_save_ms
    global _last_run_end, _last_commit_wall
    step = mark_step()
    _last_run_end = time.perf_counter()
    if rec is None:
        return
    _tls.rec = None
    with _lock:
        rec.data_wait_ms += _pending_data_wait_ms
        rec.ckpt_save_ms += _pending_ckpt_save_ms
        _pending_data_wait_ms = 0.0
        _pending_ckpt_save_ms = 0.0
    devs = device_memory_stats()
    peak = max((d["peak_bytes"] for d in devs), default=0)
    # the legacy scalar keeps its name (schema compatibility) but is now
    # the MAX across local devices; per-device gauges carry the split
    _reg.gauge("peak_hbm_bytes",
               help="device allocator high-water (bytes, max over local "
                    "devices)").set(peak)
    for d in devs:
        _reg.gauge("device_peak_hbm_bytes",
                   help="per-device allocator high-water (bytes)",
                   device=str(d["device"])).set(d["peak_bytes"])
    _reg.histogram("executor_device_ms",
                   help="compiled step call (fenced iff FLAGS_benchmark)"
                   ).observe(rec.device_ms)
    _reg.histogram("executor_data_wait_ms",
                   help="feed materialization + input-iterator wait"
                   ).observe(rec.data_wait_ms)
    payload = {
        "kind": "step",
        "step": step,
        "data_wait_ms": round(rec.data_wait_ms, 3),
        "compile_ms": round(rec.compile_ms, 3),
        "device_ms": round(rec.device_ms, 3),
        "fetch_ms": round(rec.fetch_ms, 3),
        "ckpt_save_ms": round(rec.ckpt_save_ms, 3),
        "idle_ms": round(rec.idle_ms, 3),
        "cache_hit": rec.cache_hit,
        "fenced": rec.fenced,
        "retraces": _counter("executor_retraces_total").value,
        "peak_hbm_bytes": peak,
    }
    # input-skew window (ISSUE 15): data-wait fraction of recent step
    # wall — heartbeat stamps carry it so a data-starved straggler is
    # named as such, not as a compute straggler
    now_wall = time.time()
    with _lock:
        if _last_commit_wall is not None:
            _dw_window.append((rec.data_wait_ms,
                               max(0.0, (now_wall - _last_commit_wall)
                                   * 1e3)))
        _last_commit_wall = now_wall
    try:
        # join the step's causal trace (PADDLE_TRACING): the record and
        # the span ring now cite each other; key absent when tracing is
        # off, so the documented schema is unchanged by default
        from ..telemetry import tracing

        tid = tracing.last_step_trace_id()
        if tid is not None:
            payload["trace_id"] = tid
    except Exception:  # noqa: BLE001
        pass
    if _keep_recent:
        with _lock:
            _recent_steps.append(dict(payload, ts=round(time.time(), 6)))
    sink.emit(payload)
    try:
        # goodput ledger (ISSUE 15): classify the wall window ending at
        # this commit. Unarmed cost: one cached bool read
        goodput.on_step_commit(payload, now=now_wall)
    except Exception:  # noqa: BLE001 — accounting never fails a step
        pass


def reset_for_tests() -> None:
    """Zero the per-process step state (unit tests only; the registry
    is reset separately via telemetry.get_registry().reset())."""
    global _step_count, _pending_data_wait_ms, _pending_ckpt_save_ms
    global _aux_armed, _keep_recent, _last_run_end, _last_commit_wall
    with _lock:
        _step_count = 0
        _recent.clear()
        _recent_steps.clear()
        _dw_window.clear()
        _pending_data_wait_ms = 0.0
        _pending_ckpt_save_ms = 0.0
    _aux_armed = False
    _keep_recent = False
    _last_run_end = None
    _last_commit_wall = None
    _tls.rec = None
