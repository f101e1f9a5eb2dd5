"""Profiler: RecordEvent spans, summary, chrome trace export (reference
platform/profiler.h + tools/timeline.py)."""
import json
import os

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, profiler


def _tiny_step(steps=3):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8, 4], append_batch_size=False)
        y = layers.data("y", [8, 1], append_batch_size=False)
        loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.executor.Scope()):
        exe.run(startup)
        xa = np.random.RandomState(0).rand(8, 4).astype(np.float32)
        ya = xa.sum(1, keepdims=True).astype(np.float32)
        for _ in range(steps):
            exe.run(main, feed={"x": xa, "y": ya}, fetch_list=[loss])


def test_profiler_records_executor_spans(tmp_path, capsys):
    path = str(tmp_path / "profile")
    with profiler.profiler(state="CPU", profile_path=path):
        with profiler.RecordEvent("user_span"):
            _tiny_step(steps=3)
    out = capsys.readouterr().out
    assert "Executor::run" in out and "user_span" in out

    trace = json.load(open(path + ".json"))
    names = {e["name"] for e in trace["traceEvents"]}
    assert "Executor::run" in names and "Executor::compile" in names
    assert "user_span" in names
    runs = [e for e in trace["traceEvents"] if e["name"] == "Executor::run"]
    # startup + 3 steps: Executor::run is the CALL, every phase inside it
    assert len(runs) == 4
    assert all(e["dur"] >= 0 and "ts" in e for e in runs)
    calls = [e for e in trace["traceEvents"]
             if e["name"] in ("Executor::dispatch",
                              "Executor::first_dispatch")]
    assert [e["name"] for e in sorted(calls, key=lambda e: e["ts"])] == (
        ["Executor::first_dispatch"] * 2 + ["Executor::dispatch"] * 2)
    for call in calls:
        assert any(r["ts"] <= call["ts"]
                   and call["ts"] + call["dur"] <= r["ts"] + r["dur"]
                   for r in runs)


def test_a_host_span_encloses_the_device_work_it_waited_for(tmp_path, capsys):
    """State "All": both tracks of the chrome trace come from the xplane,
    where a RecordEvent is a TraceAnnotation on the clock of the device's
    operations (on the CPU backend, XLA's own threads in the host plane).
    Each side used to be anchored to its own first timestamp, which
    shifted them against each other by whatever lay between the start of
    the trace and the first host span: here, a sleep."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    work(x).block_until_ready()  # compiled before the trace starts
    path = str(tmp_path / "both")
    profiler.start_profiler(state="All")
    time.sleep(0.05)
    with profiler.RecordEvent("host_span"):
        work(x).block_until_ready()
    profiler.stop_profiler(profile_path=path)
    assert "host_span" in capsys.readouterr().out  # the summary table
    events = [e for e in json.load(open(path + ".json"))["traceEvents"]
              if e.get("ph") == "X"]
    (span,) = [e for e in events if e["name"] == "host_span"]
    ops = [e for e in events if e["name"].startswith("dot_general")]
    assert ops, sorted({e["name"] for e in events})[:40]
    for op in ops:
        assert span["ts"] <= op["ts"]
        assert op["ts"] + op["dur"] <= span["ts"] + span["dur"]
    # one time base: the span starts the sleep after the trace's first event
    assert span["ts"] >= 50e3


def test_record_event_is_noop_when_disabled():
    profiler.reset_profiler()
    with profiler.RecordEvent("should_not_record"):
        pass
    assert not profiler.is_profiler_enabled()
    # nothing recorded outside an active profiling session
    import paddle_tpu.fluid.profiler as p

    assert not p._events


def test_start_stop_api(tmp_path, capsys):
    path = str(tmp_path / "p2")
    profiler.start_profiler(state="CPU")
    _tiny_step(steps=1)
    profiler.stop_profiler(sorted_key="calls", profile_path=path)
    assert os.path.exists(path + ".json")
    assert not profiler.is_profiler_enabled()


def test_executor_memory_analysis():
    """XLA buffer-assignment numbers for a compiled step (peak HBM
    report): argument/temp/peak byte counts of a real executable."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [8, 16], "float32")
        loss = layers.reduce_mean(layers.fc(x, 32))
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    scope = fluid.executor.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        feed = {"x": np.zeros((8, 16), "f4")}
        # before the STARTUP program runs there is no state to abstract
        try:
            exe.memory_analysis(main, feed=feed, fetch_list=[loss])
            raise AssertionError("expected RuntimeError before startup")
        except RuntimeError:
            pass
        exe.run(startup)
        # compiles on demand WITHOUT executing the step (the bench's
        # auto-remat ladder probes HBM fit exactly this way)
        ma_pre = exe.memory_analysis(main, feed=feed, fetch_list=[loss])
        assert ma_pre["peak_bytes"] > 0
        exe.run(main, feed=feed, fetch_list=[loss])
        ma = exe.memory_analysis(main, feed=feed, fetch_list=[loss])
    assert ma["argument_size_in_bytes"] > 0
    assert ma["peak_bytes"] >= ma["temp_size_in_bytes"]
