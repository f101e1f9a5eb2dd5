"""Per-op device-time attribution (ISSUE 6): xplane scope aggregation,
FLAGS_op_profile trace identity, the proftop CLI, the debugz
introspection server, and the metrics push exporter.

Layers under test:
  ops/registry.emit_ops + Executor      named-scope tagging (flag-gated,
                                        compile-cache keyed)
  fluid/profiler.xplane_op_events       op-event aggregation incl. the
                                        nested-event (while body) filter
  telemetry/cost.py                     HLO metadata parse, fused split,
                                        neighborhood propagation, report
  tools/proftop.py                      CLI end to end on resnet18
  telemetry/debugz.py                   /metrics /statusz /steps /healthz
  telemetry/export.py                   bounded retry, flag-off, formats
"""
import importlib.util
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.telemetry import cost, debugz, export, get_registry, sink


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _make_xspace(events, line_name="tf_XLACpuClient/1", plane_name="/host:CPU"):
    """Synthetic XSpace: events = [(name, offset_ps, dur_ps, is_op)];
    is_op attaches the hlo_op stat the aggregator keys on."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add(name=plane_name)
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "hlo_op"
    line = plane.lines.add(name=line_name, timestamp_ns=1000)
    for i, (name, offset_ps, dur_ps, is_op) in enumerate(events, start=1):
        plane.event_metadata[i].id = i
        plane.event_metadata[i].name = name
        ev = line.events.add(metadata_id=i, offset_ps=offset_ps,
                             duration_ps=dur_ps)
        if is_op:
            st = ev.stats.add(metadata_id=1)
            st.ref_value = i
    return xs


SYNTH_HLO = """\
HloModule jit_fn, entry_computation_layout={()->()}

%fused_computation (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %exp.1 = f32[4]{0} exponential(f32[4]{0} %p0), metadata={op_name="jit(fn)/jit(main)/op3:relu/exp"}
  ROOT %add.2 = f32[4]{0} add(f32[4]{0} %exp.1, f32[4]{0} %p0), metadata={op_name="jit(fn)/jit(main)/op4:scale/add"}
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %dot.5 = f32[4]{0} dot(f32[4]{0} %a, f32[4]{0} %a), metadata={op_name="jit(fn)/jit(main)/op0:matmul/dot_general"}
  %copy.7 = f32[4]{0} copy(f32[4]{0} %dot.5)
  %while.8 = f32[4]{0} while(f32[4]{0} %copy.7), metadata={op_name="jit(fn)/jit(main)/fwk:rng_advance/while"}
  ROOT %my_fusion = f32[4]{0} fusion(f32[4]{0} %while.8), kind=kLoop, calls=%fused_computation
}
"""


def _tiny_train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8, 16], append_batch_size=False)
        y = layers.data("y", [8, 1], append_batch_size=False)
        loss = layers.mean(
            layers.square_error_cost(layers.fc(x, 4), y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 16).astype(np.float32),
            "y": rng.rand(8, 1).astype(np.float32)}
    return main, startup, feed, loss


def _load_tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _op_profile_off():
    """Every test starts and ends with the flag off (the default)."""
    yield
    fluid.flags.set_flags({"FLAGS_op_profile": False})


# ---------------------------------------------------------------------------
# xplane aggregation
# ---------------------------------------------------------------------------


def test_xplane_aggregation_sums_and_filters():
    from paddle_tpu.fluid import profiler

    xs = _make_xspace([
        ("dot.5", 0, 600_000, True),
        ("dot.5", 1_000_000, 400_000, True),       # second step, same op
        ("ThunkExecutor::Execute", 0, 2_000_000, False),  # host span: out
        ("my_fusion", 2_000_000, 300_000, True),
    ])
    out = profiler.xplane_op_events(xs)
    assert set(out) == {"dot.5", "my_fusion"}
    assert out["dot.5"]["dur_ps"] == 1_000_000
    assert out["dot.5"]["count"] == 2
    assert out["my_fusion"]["dur_ps"] == 300_000


def test_xplane_nested_op_events_charge_the_outer_span():
    """A while instruction's span contains its body's op events — the
    body must not double-count (the scanned-encoder case)."""
    from paddle_tpu.fluid import profiler

    xs = _make_xspace([
        ("while.8", 0, 1_000_000, True),
        ("dot.inner", 100_000, 200_000, True),     # inside while.8
        ("add.inner", 400_000, 100_000, True),     # inside while.8
        ("dot.outer", 2_000_000, 500_000, True),   # disjoint
    ])
    out = profiler.xplane_op_events(xs)
    assert "dot.inner" not in out and "add.inner" not in out
    assert out["while.8"]["dur_ps"] == 1_000_000
    assert out["dot.outer"]["dur_ps"] == 500_000


# ---------------------------------------------------------------------------
# HLO metadata parse + cost report join
# ---------------------------------------------------------------------------


def test_parse_hlo_scopes_fusion_and_propagation():
    instrs = cost.parse_hlo_metadata(SYNTH_HLO)
    assert instrs["dot.5"]["scopes"] == [("op", 0, "matmul")]
    # fusion splits across its body's scopes
    assert sorted(instrs["my_fusion"]["scopes"]) == [
        ("op", 3, "relu"), ("op", 4, "scale")]
    # metadata-less copy.7 propagates from its operand (dot.5)
    assert instrs["copy.7"]["scopes"] == [("op", 0, "matmul")]
    # framework scope recognized
    assert instrs["while.8"]["scopes"] == [("fwk", "rng_advance")]


def test_cost_report_fused_split_and_coverage():
    events = {
        "dot.5": {"dur_ps": 600_000_000, "count": 3},
        "my_fusion": {"dur_ps": 400_000_000, "count": 3},  # ops 3+4 fused
        "while.8": {"dur_ps": 100_000_000, "count": 3},    # fwk
        "unknown.1": {"dur_ps": 50_000_000, "count": 3},   # unattributed
    }
    rep = cost.build_cost_report(events, SYNTH_HLO, steps=3,
                                 peak_flops=1e12)
    by_scope = {r.scope: r for r in rep.rows}
    assert by_scope["op0:matmul"].device_ms == pytest.approx(0.6)
    assert not by_scope["op0:matmul"].fused
    # 0.4ms fusion split pro-rata across op3/op4
    assert by_scope["op3:relu"].device_ms == pytest.approx(0.2)
    assert by_scope["op4:scale"].device_ms == pytest.approx(0.2)
    assert by_scope["op3:relu"].fused and by_scope["op4:scale"].fused
    assert rep.framework["rng_advance"] == pytest.approx(0.1)
    # coverage counts op + framework scopes; unknown.1 dilutes it
    assert rep.coverage == pytest.approx(1.1 / 1.15)
    assert rep.unattributed["unknown.1"] == pytest.approx(0.05)
    assert rep.device_ms_per_step == pytest.approx(1.1 / 3)
    # the report landed on the debugz hook and in the registry
    assert cost.last_report() is rep
    assert get_registry().gauge("op_profile_coverage").value == pytest.approx(
        rep.coverage)


def test_cost_report_joins_program_callstacks():
    main, startup, feed, loss = _tiny_train_program()
    ops = main.global_block().ops
    idx = next(i for i, op in enumerate(ops) if op.type == "mul")
    hlo = (f'ENTRY %main.1 (a: f32[4]) -> f32[4] {{\n'
           f'  ROOT %dot.1 = f32[4]{{0}} dot(), '
           f'metadata={{op_name="jit(fn)/op{idx}:mul/dot_general"}}\n'
           f'}}\n')
    rep = cost.build_cost_report(
        {"dot.1": {"dur_ps": 1_000_000, "count": 1}}, hlo, program=main)
    (row,) = rep.rows
    assert row.op_index == idx and row.op_type == "mul"
    # the layer names THIS test file (the user's layer call)
    assert row.layer and "test_proftop.py" in row.layer
    assert rep.by_layer  # rollup keyed by the same frame


# ---------------------------------------------------------------------------
# FLAGS_op_profile: trace identity + cache behavior
# ---------------------------------------------------------------------------


def test_op_profile_off_trace_identical_and_cache_stable():
    main, startup, feed, loss = _tiny_train_program()
    exe = fluid.Executor()
    exe.run(startup)
    baseline = exe.aot_step(main, feed=feed, fetch_list=[loss]).as_text()
    assert "op0:" not in baseline and "fwk:" not in baseline
    n_cache = len(exe._cache)

    fluid.flags.set_flags({"FLAGS_op_profile": True})
    tagged = exe.aot_step(main, feed=feed, fetch_list=[loss]).as_text()
    assert len(exe._cache) == n_cache + 1  # flag is in the cache key
    assert "0:" in tagged and "fwk:rng_advance" in tagged
    assert any(f"op_name=\"jit" in ln and ":mul" in ln
               for ln in tagged.splitlines())

    # toggling back off hits the ORIGINAL entry and the ORIGINAL trace
    fluid.flags.set_flags({"FLAGS_op_profile": False})
    again = exe.aot_step(main, feed=feed, fetch_list=[loss]).as_text()
    assert len(exe._cache) == n_cache + 1
    assert again == baseline


def test_op_profile_on_same_numerics():
    from paddle_tpu.fluid.executor import Scope

    def run(profile):
        fluid.flags.set_flags({"FLAGS_op_profile": profile})
        main, startup, feed, loss = _tiny_train_program()
        exe = fluid.Executor()
        scope = Scope()  # isolated: identical seed -> identical init
        exe.run(startup, scope=scope)
        (v,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        return np.asarray(v)

    np.testing.assert_allclose(run(False), run(True), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# proftop CLI (in-process, resnet18 tiny shapes)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~21s: the single heaviest tier-1 test, and ci.sh's
# proftop smoke already asserts the same coverage/callstack/MFU bars on
# resnet50 AND bert through this CLI — wall-time triage (870s gate)
def test_proftop_cli_resnet18(capsys):
    proftop = _load_tool("proftop")
    rc = proftop.main(["--model", "resnet18", "--steps", "2",
                       "--image-size", "32", "--json"])
    assert rc == 0
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
    rep = json.loads(line)
    assert rep["model"] == "resnet18"
    # the acceptance bar: >=90% of op time lands on named scopes
    assert rep["coverage"] >= 0.9, rep["coverage"]
    assert rep["rows"], "no attributed op rows"
    for row in rep["rows"]:
        assert row["op_index"] >= 0
        assert row["layer"], f"row {row['scope']} lost its callstack"
    # XLA's measured flops vs bench.py's model formula — documented
    # tolerance 2x. The MFU gauges divide both by a chip peak; the CPU
    # has none in the table, so they stay unset here
    ratio = rep["measured_flops_per_step"] / rep["formula_flops_per_step"]
    assert 0.5 <= ratio <= 2.0, ratio
    assert rep["measured_mfu"] is None and rep["formula_mfu"] is None


def test_proftop_trace_dir_mode(tmp_path, capsys):
    """--trace_dir aggregates an existing dump; with --hlo it joins
    scopes (no model build, no jax profiling)."""
    proftop = _load_tool("proftop")
    xs = _make_xspace([("dot.5", 0, 600_000, True),
                       ("my_fusion", 1_000_000, 400_000, True)])
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(xs.SerializeToString())
    hlo = tmp_path / "step.hlo.txt"
    hlo.write_text(SYNTH_HLO)
    rc = proftop.main(["--trace_dir", str(tmp_path), "--hlo", str(hlo),
                       "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.splitlines()[-1])
    scopes = {r["scope"] for r in rep["rows"]}
    assert {"op0:matmul", "op3:relu", "op4:scale"} <= scopes


# ---------------------------------------------------------------------------
# debugz introspection server
# ---------------------------------------------------------------------------


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.status, r.read().decode()


def test_debugz_endpoints():
    debugz.stop()
    cost._last_report = None
    get_registry().counter("debugz_test_total", "t").inc(3)
    srv = debugz.serve(port=0)
    try:
        port = srv.server_address[1]
        status, body = _get(port, "/healthz")
        assert status == 200 and body.strip() == "ok"

        # /metrics: valid Prometheus exposition (TYPE headers + samples)
        status, body = _get(port, "/metrics")
        assert status == 200
        assert "# TYPE debugz_test_total counter" in body
        assert any(ln.split() == ["debugz_test_total", "3"]
                   for ln in body.splitlines())

        status, body = _get(port, "/statusz")
        st = json.loads(body)
        assert {"build", "flags", "mesh", "steps", "pid"} <= set(st)
        assert "FLAGS_op_profile" in st["flags"]

        status, body = _get(port, "/steps")
        assert status == 200 and isinstance(json.loads(body), list)

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(port, "/proftop")  # no report built yet
        assert ei.value.code == 404
        cost.build_cost_report(
            {"dot.5": {"dur_ps": 1_000_000, "count": 1}}, SYNTH_HLO)
        status, body = _get(port, "/proftop")
        assert status == 200 and "coverage" in json.loads(body)
    finally:
        debugz.stop()


def test_debugz_armed_by_step_loop(monkeypatch):
    """PADDLE_DEBUGZ_PORT arms the server from the executor step loop
    (launch.py sets the var per rank) and /steps serves breakdowns even
    with the JSONL sink off."""
    debugz.stop()
    monitor.reset_for_tests()
    monkeypatch.setenv("PADDLE_DEBUGZ_PORT", "0")  # ephemeral
    try:
        main, startup, feed, loss = _tiny_train_program()
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
        assert debugz.armed()
        port = debugz._server.server_address[1]
        status, body = _get(port, "/steps")
        steps = json.loads(body)
        assert steps, "step records missing with debugz armed"
        assert {"step", "device_ms", "compile_ms",
                "cache_hit"} <= set(steps[-1])
    finally:
        debugz.stop()
        monitor.reset_for_tests()


# ---------------------------------------------------------------------------
# push exporter
# ---------------------------------------------------------------------------


class _Collector:
    """Tiny local collector: records POSTs, optionally failing the
    first N with HTTP 500."""

    def __init__(self, fail_first=0):
        from http.server import BaseHTTPRequestHandler, HTTPServer

        self.bodies = []
        self.headers = []
        self.attempts = 0
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                outer.attempts += 1
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                if outer.attempts <= fail_first:
                    self.send_response(500)
                    self.end_headers()
                    return
                outer.bodies.append(body)
                outer.headers.append(dict(self.headers))
                self.send_response(200)
                self.end_headers()

            def log_message(self, fmt, *args):
                pass

        self.server = HTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def url(self, path="/ingest"):
        return f"http://127.0.0.1:{self.port}{path}"

    def close(self):
        self.server.shutdown()
        self.server.server_close()


def test_exporter_flag_off_means_no_exporter(monkeypatch):
    export.stop()
    monkeypatch.delenv(export.ENV_URL, raising=False)
    assert export.maybe_start() is None
    assert export.active() is None
    export.stop()


def test_exporter_pushes_otlp_shaped_snapshot():
    export.stop()
    col = _Collector()
    try:
        get_registry().counter("export_test_total", "t").inc(7)
        exp = export.PushExporter(col.url(), interval_s=60, retries=2)
        assert exp.flush()
        payload = json.loads(col.bodies[-1])
        assert payload["resource"]["pid"] == os.getpid()
        series = payload["metrics"]["export_test_total"]["series"]
        assert series[0]["value"] == 7
        assert get_registry().counter("metrics_push_total").value >= 1
    finally:
        col.close()
        export.stop()


def test_exporter_retry_is_bounded_with_backoff():
    export.stop()
    fails = get_registry().counter("metrics_push_failures_total").value
    col = _Collector(fail_first=100)  # always failing
    try:
        exp = export.PushExporter(col.url(), interval_s=60, retries=3,
                                  backoff_s=0.01)
        assert not exp.flush()
        assert col.attempts == 3  # bounded: exactly `retries` attempts
        assert (get_registry().counter("metrics_push_failures_total").value
                == fails + 1)
        # recovery: collector comes back, next interval delivers
        col2 = _Collector()
        exp.url = col2.url()
        assert exp.flush()
        col2.close()
    finally:
        col.close()
        export.stop()


def test_exporter_retries_then_succeeds():
    export.stop()
    col = _Collector(fail_first=2)
    try:
        exp = export.PushExporter(col.url(), interval_s=60, retries=3,
                                  backoff_s=0.01)
        assert exp.flush()
        assert col.attempts == 3 and len(col.bodies) == 1
    finally:
        col.close()
        export.stop()


def test_exporter_pushgateway_format_is_prometheus_text():
    export.stop()
    col = _Collector()
    try:
        get_registry().counter("export_pg_total", "t").inc()
        exp = export.PushExporter(col.url("/metrics/job/paddle"),
                                  interval_s=60)
        assert exp.fmt == "prom"
        assert exp.flush()
        assert b"# TYPE export_pg_total counter" in col.bodies[-1]
        assert "text/plain" in col.headers[-1].get("Content-Type", "")
    finally:
        col.close()
        export.stop()


def test_exporter_env_arming(monkeypatch):
    export.stop()
    col = _Collector()
    try:
        monkeypatch.setenv(export.ENV_URL, col.url())
        monkeypatch.setenv(export.ENV_SECS, "60")
        exp = export.maybe_start()
        assert exp is not None and exp.flush()
    finally:
        col.close()
        export.stop()


# ---------------------------------------------------------------------------
# satellite: registry exposition fixes + sink pid fallback
# ---------------------------------------------------------------------------


def test_prometheus_label_value_escaping():
    from paddle_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("esc_total", "t", path='C:\\tmp\n"x"').inc()
    text = reg.to_prometheus()
    line = [ln for ln in text.splitlines()
            if ln.startswith("esc_total{")][0]
    assert '\\\\tmp' in line and '\\"x\\"' in line and '\\n' in line
    assert "\n" not in line  # the raw newline must not tear the sample


def test_empty_histogram_is_well_defined():
    from paddle_tpu.telemetry.registry import Histogram

    h = Histogram()
    s = h.summary()
    assert s == {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                 "avg": 0.0}
    assert h.quantile(0.5) == 0.0
    assert h.quantile(0.99) == 0.0


def test_sink_placeholder_falls_back_to_pid(monkeypatch):
    from paddle_tpu.telemetry.sink import _expand

    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    # un-launched processes sharing a template must not collide
    assert _expand("/tmp/m.{rank}.jsonl", 0) == \
        f"/tmp/m.pid{os.getpid()}.jsonl"
    assert _expand("/tmp/m.%r.jsonl", 0) == \
        f"/tmp/m.pid{os.getpid()}.jsonl"
    # explicit placeholder-free paths stay exactly as given (CI contract)
    assert _expand("/tmp/m.jsonl", 0) == "/tmp/m.jsonl"
    # launched processes keep the rank expansion
    monkeypatch.setenv("PADDLE_TRAINER_ID", "2")
    assert _expand("/tmp/m.{rank}.jsonl", 2) == "/tmp/m.2.jsonl"
    assert _expand("/tmp/m.jsonl", 2) == "/tmp/m.rank2.jsonl"


# ---------------------------------------------------------------------------
# tools/op_bench.py (the one-op measurement path) and the cost report's
# per-op query it reads
# ---------------------------------------------------------------------------


def test_op_bench_run_case_schema_and_sweep():
    op_bench = _load_tool("op_bench")

    row = op_bench.run_case("matmul", {"X": (8, 8), "Y": (8, 8)}, {},
                            repeat=2, op_profile=False)
    assert row["op"] == "matmul" and row["fenced"] is True
    assert row["latency_us"] > 0 and row["repeat"] == 2
    combos = list(op_bench.sweep_cases(
        [("X", [(8, 8), (16, 16)]), ("Y", [(8, 8)])]))
    assert combos == [{"X": (8, 8), "Y": (8, 8)},
                      {"X": (16, 16), "Y": (8, 8)}]


def test_op_bench_op_profile_objective():
    op_bench = _load_tool("op_bench")

    row = op_bench.run_case("matmul", {"X": (32, 32), "Y": (32, 32)}, {},
                            repeat=2, op_profile=True, op_profile_steps=2)
    # the op's OWN attributed device time, not the whole step's
    assert row["op_device_us"] > 0
    assert 0 < row["op_profile_coverage"] <= 1.0


def test_cost_report_per_op_query():
    from paddle_tpu.telemetry.cost import CostReport, CostRow

    rows = [
        CostRow(scope="op0:matmul", op_index=0, op_type="matmul",
                device_ms=6.0, share=0.6, count=2, fused=False),
        CostRow(scope="op1:softmax", op_index=1, op_type="softmax",
                device_ms=4.0, share=0.4, count=2, fused=False),
    ]
    rep = CostReport(rows=rows, by_op_type={}, by_layer={}, framework={},
                     unattributed={}, steps=2, total_op_ms=10.0,
                     attributed_ms=10.0, coverage=1.0,
                     device_ms_per_step=5.0)
    assert rep.device_ms_for(op_type="matmul") == 3.0  # per step
    assert rep.device_ms_for(op_type="matmul", per_step=False) == 6.0
    assert rep.device_ms_for(op_index=1) == 2.0
    assert rep.device_ms_for(op_type="missing") == 0.0
    assert len(rep.rows_for(op_type="softmax")) == 1
