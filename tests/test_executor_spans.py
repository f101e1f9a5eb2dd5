"""The host half of the tracing: one span, `profiler.RecordEvent`, inside
`Executor.run`, its compile path and the loader. Under a `jax.profiler`
trace the spans lie in the xplane's host plane; the same object fills the
step record, the profiler's list and the tracing ring while each is armed,
and nothing where none is; the compile counters move inside the executor's
compile spans only."""
import json
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import telemetry
from paddle_tpu.fluid import layers, monitor, profiler
from paddle_tpu.telemetry import sink, tracing

RUN_PHASES = ("Executor::feed", "Executor::lookup", "Executor::state",
              "Executor::commit", "Executor::fetch")
COMPILE_COUNTERS = ("executor_trace_seconds_total",
                    "executor_lower_seconds_total",
                    "executor_backend_compile_seconds_total",
                    "executor_persistent_cache_hits_total")


def _program(width=4):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = layers.data("x", [8, width], append_batch_size=False)
        y = layers.data("y", [8, 1], append_batch_size=False)
        loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _batches(n, width=4):
    def reader():
        rng = np.random.RandomState(0)
        for _ in range(n):
            xa = rng.rand(8, width).astype(np.float32)
            yield [xa, xa.sum(1, keepdims=True)]
    return reader


def _counters():
    reg = telemetry.get_registry()
    return {n: reg.counter(n).value for n in COMPILE_COUNTERS}


# ---------------------------------------------------------------------------
# the xplane
# ---------------------------------------------------------------------------


def _host_lines(trace_dir):
    """[(name, start_ns, end_ns, step_num or None), ...] of every line of
    the host plane."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
              dict(e.stats).get("step_num") if e.name == "Executor::run"
              else None)
             for e in ln.events] for ln in host.lines]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_the_spans_lie_in_the_profilers_host_plane(tmp_path):
    import jax

    main, startup, loss = _program()
    exe, scope = fluid.Executor(), fluid.executor.Scope()
    loader = fluid.DataLoader.from_generator(feed_list=["x", "y"])
    loader.set_batch_generator(_batches(4))
    step0 = monitor.global_step()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.loop"):
            exe.run(startup, scope=scope)
            for feed in loader:
                exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            exe.aot_step(main, feed=feed, fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()

    lines = _host_lines(str(tmp_path))
    (loop,) = [ln for ln in lines if any(e[0] == "test.loop" for e in ln)]
    (window,) = [e for e in loop if e[0] == "test.loop"]
    ours = [e for e in loop
            if e[0].startswith(("Executor::", "DataLoader::"))]
    assert all(_inside(e, window) for e in ours)
    runs = [e for e in ours if e[0] == "Executor::run"]
    assert len(runs) == 5  # the startup program and four steps

    def within(run):
        return [e[0] for e in sorted(ours, key=lambda e: e[1])
                if e is not run and _inside(e, run)]

    # a signature's first call traces, lowers and compiles: another name
    first = ["Executor::feed", "Executor::lookup", "Executor::compile",
             "Executor::state", "Executor::first_dispatch",
             "Executor::commit", "Executor::fetch"]
    after = ["Executor::feed", "Executor::lookup", "Executor::state",
             "Executor::dispatch", "Executor::commit", "Executor::fetch"]
    assert [within(r) for r in runs] == [first, first, after, after, after]
    # every phase is inside a run, the compile inside its look-up
    lookups = [e for e in ours if e[0] == "Executor::lookup"]
    for e in ours:
        if e[0] == "Executor::compile":
            assert any(_inside(e, lk) for lk in lookups)
        elif e[0].startswith("Executor::") and e[0] not in (
                "Executor::run", "Executor::aot"):
            assert any(_inside(e, r) for r in runs), e[0]
    (aot,) = [e for e in ours if e[0] == "Executor::aot"]
    assert not any(_inside(aot, r) for r in runs)
    # the loop takes a batch between two runs (and once more, to find the
    # reader at its end), the host arrays made inside the wait's span
    nexts = [e for e in ours if e[0] == "DataLoader::next"]
    made = [e for e in ours if e[0] == "DataLoader::materialize"]
    assert len(nexts) == 5 and len(made) == 4
    assert all(any(_inside(m, n) for n in nexts) for m in made)
    assert not any(_inside(n, r) for n in nexts for r in runs)
    # the producer thread's pulls from the user's reader: another line
    assert not any(e[0] == "DataLoader::produce" for e in loop)
    (producer,) = [ln for ln in lines
                   if any(e[0] == "DataLoader::produce" for e in ln)]
    assert len([e for e in producer if e[0] == "DataLoader::produce"]) == 5

    # the run is a step annotation, numbered by the monitor's step
    assert [r[3] for r in runs] == list(range(step0, step0 + 5))


# ---------------------------------------------------------------------------
# the counters at the compile boundary
# ---------------------------------------------------------------------------


@pytest.fixture()
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of the test's,
    taking every executable however small."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    for n, v in zip(names, (str(tmp_path / "cache"), 0, -1)):
        jax.config.update(n, v)
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_the_compile_counters_move_inside_the_compile_spans_only(
        persistent_cache):
    import jax
    import jax.numpy as jnp

    seconds = COMPILE_COUNTERS[:3]
    feed = dict(zip(("x", "y"), next(iter(_batches(1, width=6)()))))

    def moved(since):
        now = _counters()
        return {n: now[n] - since[n] for n in COMPILE_COUNTERS}

    # a miss: the closure is built, and the first call traces, lowers and
    # compiles; nothing of this module is in the cache yet
    main, startup, loss = _program(width=6)
    exe, scope = fluid.Executor(), fluid.executor.Scope()
    exe.run(startup, scope=scope)
    start, t0 = _counters(), time.perf_counter()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    wall = time.perf_counter() - t0
    miss = moved(start)
    assert all(miss[n] > 0 for n in seconds), miss
    assert miss["executor_persistent_cache_hits_total"] == 0
    # the outermost durations only: never more than the clock around them
    assert sum(miss[n] for n in seconds) < wall

    # hits, and a jit of the test's own that compiles meanwhile: still
    start = _counters()
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    jax.jit(lambda a: jnp.tanh(a @ a.T).sum())(jnp.ones((6, 6)))
    assert moved(start) == dict.fromkeys(COMPILE_COUNTERS, 0)

    # aot_step of a program that has not run: its own span counts, and the
    # same module built again is served by the persistent cache
    main2, startup2, loss2 = _program(width=6)
    exe2, scope2 = fluid.Executor(), fluid.executor.Scope()
    exe2.run(startup2, scope=scope2)
    start = _counters()
    exe2.aot_step(main2, feed=feed, fetch_list=[loss2], scope=scope2)
    aot = moved(start)
    assert aot["executor_trace_seconds_total"] > 0
    assert aot["executor_lower_seconds_total"] > 0
    assert aot["executor_persistent_cache_hits_total"] >= 1
    assert aot["executor_backend_compile_seconds_total"] > 0  # the read


def test_of_nested_durations_the_outermost_counts():
    """A kernel's inner jit is traced inside the step's trace and reports
    a duration of its own within it: JAX announces a duration by a scalar
    of its name when it starts, so the depth is known."""
    import jax

    event = "/jax/core/compile/jaxpr_trace_duration"
    name = "executor_trace_seconds_total"

    def report():
        jax.monitoring.record_scalar(event, 0.0)  # the step's trace begins
        jax.monitoring.record_scalar(event, 0.0)  # a kernel's, inside it
        jax.monitoring.record_event_duration_secs(event, 0.25)
        jax.monitoring.record_event_duration_secs(event, 1.0)
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/some_other_duration", 4.0)

    with monitor.CompileEvent("Executor::aot"):
        pass  # the listeners are registered by the first such span
    start = _counters()[name]
    report()  # outside the executor's compile spans: another jit's
    assert _counters()[name] == start
    with monitor.CompileEvent("Executor::aot"):
        with monitor.CompileEvent("Executor::compile"):
            report()
        report()
    assert _counters()[name] == start + 2.0
    report()
    assert _counters()[name] == start + 2.0


# ---------------------------------------------------------------------------
# the other consumers, each while armed
# ---------------------------------------------------------------------------


def _train(steps=3):
    main, startup, loss = _program()
    exe, scope = fluid.Executor(), fluid.executor.Scope()
    exe.run(startup, scope=scope)
    for xa, ya in _batches(steps)():
        exe.run(main, feed={"x": xa, "y": ya}, fetch_list=[loss],
                scope=scope)


def test_the_step_record_is_filled_by_the_spans(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    sink.enable(path)
    try:
        _train(steps=3)
    finally:
        sink.disable()
    recs = [r for r in map(json.loads, open(path)) if r["kind"] == "step"]
    assert len(recs) == 4  # the startup program and three steps
    for r in recs[1:]:  # (the startup program feeds and fetches nothing)
        assert r["data_wait_ms"] > 0 and r["fetch_ms"] > 0
    # a miss step: the closure and the first call are compile time, and no
    # device time; a hit step the other way round
    for r in recs[:2]:
        assert r["cache_hit"] is False
        assert r["compile_ms"] > 0 and r["device_ms"] == 0
    for r in recs[2:]:
        assert r["cache_hit"] is True
        assert r["compile_ms"] == 0 and r["device_ms"] > 0
    assert recs[1]["compile_ms"] > 10 * recs[2]["device_ms"]


def test_the_tracing_ring_holds_the_steps_children(monkeypatch):
    monkeypatch.setattr(tracing, "_enabled", True)
    tracing._ring.clear()
    try:
        _train(steps=2)
        spans = tracing.finished_spans()
    finally:
        tracing._ring.clear()
    roots = [s for s in spans if s["kind"] == "step"]
    assert len(roots) == 3 and all(s["name"] == "step" for s in roots)
    ids = {s["span"] for s in roots}
    last = [s for s in spans if s["trace"] == roots[-1]["trace"]
            and s["kind"] != "step"]
    assert sorted(s["name"] for s in last) == sorted(
        RUN_PHASES + ("Executor::dispatch",))
    assert all(s["parent"] in ids for s in last)


def test_the_profilers_list_holds_the_spans_under_start_profiler(tmp_path):
    profiler.start_profiler(state="CPU")
    try:
        _train(steps=2)
    finally:
        profiler.stop_profiler(profile_path=str(tmp_path / "p"))
    names = {e["name"] for e in json.load(open(tmp_path / "p.json"))[
        "traceEvents"]}
    assert set(RUN_PHASES) | {
        "Executor::run", "Executor::compile", "Executor::first_dispatch",
        "Executor::dispatch"} <= names


def test_with_nothing_armed_a_run_builds_no_record(monkeypatch):
    monitor.reset_for_tests()
    assert not (sink.enabled() or tracing.enabled()
                or profiler.is_profiler_enabled())

    def no_record(*a, **kw):
        raise AssertionError("a StepRecord was built with nothing armed")

    monkeypatch.setattr(monitor, "StepRecord", no_record)
    before = len(profiler._events), len(tracing.finished_spans())
    _train(steps=2)
    assert monitor.current_record() is None
    assert monitor.recent_steps() == []  # no dict either
    assert (len(profiler._events), len(tracing.finished_spans())) == before
