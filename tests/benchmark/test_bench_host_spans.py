"""The program's host spans as the benchmark reads them, on the CPU: the split
of a hand-made trace against hand values, the eight readers, what a run
without a trace or without the program's spans reports, and a traced
rehearsal."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import harness, host_spans, manifest
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane

SPAN_READERS = (
    "executor_feed_ms_per_step", "executor_dispatch_ms_per_step",
    "executor_bookkeeping_ms_per_step", "loader_next_ms_per_step",
    "idle_in_dispatch_ms_per_step", "idle_in_host_prep_ms_per_step")
COUNTER_READERS = ("executor_trace_lower_s", "executor_backend_compile_s")
READERS = {name: manifest.load_module("layer_metrics", name)
           for name in SPAN_READERS + COUNTER_READERS}
MS = 1e6  # ns


def _step(at):
    """One step of the loop from `at` ms on: 1.5 ms for the batch, a call
    of 10 ms with 9 inside `Executor::run`."""
    def ev(name, lo, hi):
        return Event(name, (at + lo) * MS, (at + hi) * MS)

    return [
        ev("bench.next_batch", 0.5, 2.0),
        ev("DataLoader::next", 0.6, 1.6),
        ev("DataLoader::materialize", 1.0, 1.4),
        ev("bench.run_call", 2.0, 12.0),
        ev("Executor::run", 2.5, 11.5),
        ev("Executor::feed", 3.0, 4.0),
        ev("Executor::lookup", 4.0, 4.5),
        ev("Executor::state", 4.5, 5.5),
        ev("Executor::dispatch", 5.5, 9.5),
        ev("Executor::commit", 9.5, 10.5),
    ]


def _planes(program_spans=True):
    """A window of 100 ms and two steps. The loop's line: a commit that
    began before the window, two steps, a sync that outlasts the window.
    Another thread's line: the loader's producer, and a run of another
    executor. Device 0 idles 0-8 ms and 53-56 ms, device 1 never."""
    loop = ([Event(tr.WINDOW_SPAN, 0.0, 100 * MS),
             Event("Executor::commit", -2 * MS, 0.4 * MS)]
            + _step(0) + _step(50)
            + [Event("bench.sync", 62 * MS, 110 * MS)])
    if not program_spans:
        loop = [e for e in loop if e.name.startswith("bench.")]
    other = [Event("DataLoader::produce", 1 * MS, 30 * MS),
             Event("DataLoader::produce", 51 * MS, 80 * MS),
             Event("Executor::run", 20 * MS, 40 * MS),
             Event("Executor::dispatch", 21 * MS, 39 * MS)]
    host = Plane(tr.HOST_PLANE, [Line("python", other),
                                 Line("python", loop)])
    dev0 = Plane("/device:TPU:0", [Line(tr.OPS_LINE, [
        Event("fusion.1", 8 * MS, 53 * MS),
        Event("fusion.2", 56 * MS, 120 * MS)])])
    dev1 = Plane("/device:TPU:1", [Line(tr.OPS_LINE, [
        Event("fusion.1", -1 * MS, 120 * MS)])])
    return [dev1, host, dev0]


def test_the_loops_line_is_the_one_that_holds_the_window():
    line, window = host_spans.loop_line(_planes())
    assert window == (0.0, 100 * MS)
    assert any(e.name == "bench.sync" for e in line.events)
    assert not any(e.name == "DataLoader::produce" for e in line.events)
    assert host_spans.loop_line(_planes()[::2]) is None  # no host plane
    assert host_spans.split(_planes()[::2], 2) is None


def test_self_times_against_hand_values():
    found = host_spans.split(_planes(), steps=2)
    assert found.window_ns == 100 * MS and found.steps == 2
    # two steps, the cut commit's 0.4 ms, the sync cut at the window's
    # end; the other thread's spans are not there
    assert {n: v / MS for n, v in found.self_ns.items()} == pytest.approx({
        "bench.next_batch": 2 * 0.5, "DataLoader::next": 2 * 0.6,
        "DataLoader::materialize": 2 * 0.4,
        "bench.run_call": 2 * 1.0, "Executor::run": 2 * 1.5,
        "Executor::feed": 2 * 1.0, "Executor::lookup": 2 * 0.5,
        "Executor::state": 2 * 1.0, "Executor::dispatch": 2 * 4.0,
        "Executor::commit": 2 * 1.0 + 0.4, "bench.sync": 38.0})
    assert found.calls["Executor::dispatch"] == 2
    assert found.calls["Executor::commit"] == 3
    assert "DataLoader::produce" not in found.self_ns
    # a step: the three parts of the call add up to the time inside
    # Executor::run, which is the call less the loop's own millisecond
    feed = found.self_ms_per_step(host_spans.FEED)
    dispatch = found.self_ms_per_step(host_spans.DISPATCH)
    rest = found.self_ms_per_step(
        host_spans.EXECUTOR, but=(host_spans.FEED, host_spans.DISPATCH))
    assert (feed, dispatch, rest) == pytest.approx((1.0, 4.0, 4.2))
    assert feed + dispatch + rest - 0.2 == pytest.approx(
        found.duration_ns["Executor::run"] / MS / 2)
    assert found.duration_ns["bench.run_call"] / MS / 2 == pytest.approx(10.0)
    assert found.self_ms_per_step(host_spans.LOADER) == pytest.approx(1.0)
    assert found.self_ms_per_step("NoSuch::") is None


def test_a_gap_under_the_call_falls_to_the_span_inside_it():
    found = host_spans.split(_planes(), steps=2)
    # device 0's gaps, 8 + 3 ms; as the harness attributes them, over its
    # own three spans, the call holds 9 of them
    harness_view = tr.reduce_trace(_planes(), steps=2).idle_by_span_s
    assert harness_view["bench.run_call"] == pytest.approx(9e-3)
    assert {n: s * 1e3 for n, s in found.idle_s.items()} == pytest.approx({
        "Executor::commit": 0.4, "(no span)": 0.1, "bench.next_batch": 0.5,
        "DataLoader::next": 0.6, "DataLoader::materialize": 0.4,
        "bench.run_call": 0.5, "Executor::run": 0.5,
        "Executor::feed": 2.0, "Executor::lookup": 1.0,
        "Executor::state": 2.0, "Executor::dispatch": 3.0})
    assert sum(found.idle_s.values()) == pytest.approx(11e-3)
    in_dispatch = found.idle_ms_per_step(host_spans.DISPATCH)
    in_prep = found.idle_ms_per_step(
        host_spans.EXECUTOR, host_spans.LOADER, but=(host_spans.DISPATCH,))
    assert (in_dispatch, in_prep) == pytest.approx((1.5, 3.45))
    # what the program holds of the call's idle time: all but the loop's
    # own half millisecond, and the batch's millisecond besides
    assert (in_dispatch + in_prep) * 2 == pytest.approx(9.0 - 0.5 + 0.4 + 1.0)
    # the longest gap first, with what it lay under
    at, ns, under = found.longest_gaps[0]
    assert (at, ns) == (0.0, 8 * MS)
    assert under["Executor::dispatch"] == pytest.approx(2.5 * MS)
    assert [g[1] for g in found.longest_gaps] == [8 * MS, 3 * MS]
    listing = host_spans.describe(found)
    assert "Executor::dispatch" in listing and "the longest gaps" in listing


def test_without_a_device_there_is_no_idle_and_the_listing_says_so():
    found = host_spans.split(_planes()[1:2], steps=2)
    assert found.idle_s is None and found.longest_gaps == []
    assert found.idle_ms_per_step(host_spans.DISPATCH) is None
    assert found.self_ms_per_step(host_spans.DISPATCH) == pytest.approx(4.0)
    assert "no device plane" in host_spans.describe(found)


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    """A run whose trace directory holds a file that loads as the planes
    the test puts into `held`."""
    held = {}
    path = tmp_path / "plugins" / "profile" / "2026_01_01" / "hand.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(tr, "load_xplane", lambda p: held["planes"])
    host_spans.split_of_trace.cache_clear()
    yield held, types.SimpleNamespace(trace=types.SimpleNamespace(steps=2))
    host_spans.split_of_trace.cache_clear()


def test_the_six_span_readers(traced):
    held, run = traced
    held["planes"] = _planes()
    got = {name: READERS[name].read(run) for name in SPAN_READERS}
    assert got == pytest.approx({
        "executor_feed_ms_per_step": 1.0,
        "executor_dispatch_ms_per_step": 4.0,
        "executor_bookkeeping_ms_per_step": 4.2,
        "loader_next_ms_per_step": 1.0,
        "idle_in_dispatch_ms_per_step": 1.5,
        "idle_in_host_prep_ms_per_step": 3.45})


def test_a_program_without_the_spans_reports_none_of_the_six(traced):
    held, run = traced
    held["planes"] = _planes(program_spans=False)  # the parent commit
    assert [READERS[n].read(run) for n in SPAN_READERS] == [None] * 6
    found = host_spans.split_of(run)
    assert sorted(found.self_ns) == ["bench.next_batch", "bench.run_call",
                                     "bench.sync"]
    assert found.idle_s["bench.run_call"] == pytest.approx(9e-3)


def test_an_untraced_run_or_a_missing_trace_reports_none(tmp_path,
                                                         monkeypatch):
    untraced = types.SimpleNamespace(trace=None)
    assert [READERS[n].read(untraced) for n in SPAN_READERS] == [None] * 6
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "none"))
    run = types.SimpleNamespace(trace=types.SimpleNamespace(steps=2))
    assert [READERS[n].read(run) for n in SPAN_READERS] == [None] * 6


def test_the_two_counter_readers(monkeypatch):
    import paddle_tpu.telemetry as telemetry
    from paddle_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    run = types.SimpleNamespace(trace=None)
    # a program that does not count them
    assert [READERS[n].read(run) for n in COUNTER_READERS] == [None, None]
    reg.counter("executor_trace_seconds_total").inc(2.5)
    reg.counter("executor_lower_seconds_total").inc(1.25)
    reg.counter("executor_backend_compile_seconds_total").inc(7.0)
    assert [READERS[n].read(run) for n in COUNTER_READERS] == [3.75, 7.0]


def test_the_readers_constants_are_the_manifests():
    doc = manifest.load_manifest()
    rows = {m["name"]: m for m in doc["per_layer"]}
    for name, reader in READERS.items():
        assert "workloads" not in rows[name]  # every cell
        assert rows[name]["better"] == "lower"
        assert (reader.LAYER, reader.MOVES, reader.UNIT, reader.SOURCE) == (
            rows[name]["layer"], rows[name]["moves"], rows[name]["unit"],
            rows[name]["source"])
    assert [(rows[n]["layer"], rows[n]["unit"], rows[n]["source"],
             rows[n]["moves"]) for n in READERS] == (
        [("step", "ms", "device_trace", "step_ms")] * 3
        + [("input", "ms", "device_trace", "step_ms")]
        + [("device", "ms", "device_trace", "step_ms")] * 2
        + [("step", "s", "host_clock", "setup_s")] * 2)
    assert manifest.problems(doc) == []


def test_a_traced_rehearsal_leaves_the_programs_spans_in_its_trace(tmp_path):
    """`--rehearse --trace 1` finds the eight readers by name. A CPU has no
    device plane, so the six that read the trace report nothing, and the
    two seconds are printed as null; the trace it leaves holds the
    program's spans on the loop's line. Run from a copy of the benchmark,
    whose trace directory no other test shares."""
    root = str(tmp_path)
    shutil.copytree(manifest.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    assert manifest.problems(manifest.load_manifest(root), root) == []
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "resnet50.train224", "--rehearse", "--seed", "7",
         "--seconds", "1", "--trace", "1"],
        cwd=root, text=True, capture_output=True, timeout=600,
        env=dict(os.environ, PYTHONPATH=manifest.ROOT))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    cell = manifest.load_cell(manifest.load_manifest(), "resnet50.train224",
                              rehearse=True)
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    assert not set(SPAN_READERS) & set(result["metrics"])
    for name in COUNTER_READERS:
        assert result["metrics"][name] == {"value": None, "unit": "s"}

    steps = harness.TRACED_GROUPS * cell.traffic["log_every"]
    found = host_spans.split(tr.load_xplane(tr.find_xplane(
        os.path.join(root, ".bench_trace"))), steps)
    assert found.idle_s is None
    inside = ("Executor::feed", "Executor::lookup", "Executor::state",
              "Executor::dispatch", "Executor::commit")
    for name in inside + ("Executor::run", "DataLoader::next",
                          "bench.run_call"):
        assert found.calls[name] == steps, name
    assert "Executor::first_dispatch" not in found.calls  # a warm window
    assert "DataLoader::produce" not in found.calls  # another thread
    # the self times of the phases and of the run add up to the runs, and
    # the runs lie inside the benchmark's calls
    assert sum(found.self_ns[n] for n in inside + ("Executor::run",)) == (
        pytest.approx(found.duration_ns["Executor::run"], rel=1e-6))
    assert (found.duration_ns["Executor::run"]
            <= found.duration_ns["bench.run_call"])
