"""Distributed step tracing (ISSUE 9): causal span propagation across
the RPC plane, the flight recorder, and critical-path attribution.

  unit layer    — span identity/parentage/ring semantics; ONE trace_id
                  through retries, hedges and replication forwards over
                  REAL connections (in-thread servers share the process
                  ring, so both ends of every hop are assertable);
                  flag-off bit-identity (no spans, no wire key, loss
                  trace unchanged); histogram trace exemplars; tracetop
                  critical-path reconstruction on a synthetic
                  3-process dump; /tracez scrape; OTLP span export.
  process layer — (slow) flight-recorder dumps on injected crash and
                  SIGTERM; the CI trace drill: a 2-trainer sync job
                  with a deterministic 400ms stall on ONE trainer's
                  push_gradients must yield a merged trace whose
                  per-round critical path names the delayed
                  (rank, verb) hop with >= 400ms attributed.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed import faults, ps_server
from paddle_tpu.fluid import flags as fl
from paddle_tpu.telemetry import get_registry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_ps_worker.py")


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


@pytest.fixture
def traced(monkeypatch):
    """Arm PADDLE_TRACING for this test; ring + gate reset on teardown."""
    monkeypatch.setenv(tracing.ENV_GATE, "1")
    tracing._reset_for_tests()
    yield
    tracing._reset_for_tests()


@pytest.fixture
def untraced(monkeypatch):
    monkeypatch.delenv(tracing.ENV_GATE, raising=False)
    tracing._reset_for_tests()
    yield
    tracing._reset_for_tests()


@pytest.fixture
def server():
    """One pserver on a free port, in a daemon thread."""
    addr = {}
    ready = threading.Event()

    def cb(a):
        addr["ep"] = f"127.0.0.1:{a[1]}"
        ready.set()

    t = threading.Thread(
        target=ps_server.serve, args=(0, "127.0.0.1", cb), daemon=True)
    t.start()
    assert ready.wait(10)
    yield addr["ep"]
    try:
        ps_server._Conn(addr["ep"]).call("shutdown")
    except Exception:
        pass
    t.join(timeout=5)


@pytest.fixture
def two_servers():
    """Two in-thread pservers (replication tests); both ends of every
    hop record into THIS process's span ring."""
    eps, threads = [], []
    for _ in range(2):
        addr = {}
        ready = threading.Event()

        def cb(a, addr=addr, ready=ready):
            addr["ep"] = f"127.0.0.1:{a[1]}"
            ready.set()

        t = threading.Thread(target=ps_server.serve,
                             args=(0, "127.0.0.1", cb), daemon=True)
        t.start()
        assert ready.wait(10)
        eps.append(addr["ep"])
        threads.append(t)
    yield eps
    for ep in eps:
        try:
            ps_server._Conn(ep).call("shutdown")
        except Exception:
            pass
    for t in threads:
        t.join(timeout=5)


@pytest.fixture
def inject(monkeypatch):
    def _arm(spec: str):
        monkeypatch.setenv(faults.ENV_SPEC, spec)
        fl.set_flags({"FLAGS_ps_fault_injection": True})
        faults.reset()

    yield _arm
    fl.set_flags({"FLAGS_ps_fault_injection": False})
    faults.reset()


def _spans():
    return tracing.finished_spans()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


# ---------------------------------------------------------------------------
# span layer semantics
# ---------------------------------------------------------------------------


def test_span_identity_and_parentage(traced):
    with tracing.span("root") as root:
        assert len(root.trace_id) == 32 and len(root.span_id) == 16
        with tracing.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
    spans = _spans()
    assert [s["name"] for s in spans] == ["child", "root"]
    assert spans[1]["parent"] is None
    assert spans[0]["dur_ms"] <= spans[1]["dur_ms"]


def test_span_error_status_and_annotate(traced):
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            tracing.annotate(detail="x")
            raise ValueError("nope")
    (s,) = _spans()
    assert s["status"] == "error:ValueError"
    assert s["attrs"]["detail"] == "x"


def test_bound_carries_context_into_pool_thread(traced):
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(1) as pool:
        with tracing.span("root") as root:
            def work():
                with tracing.span("inner"):
                    pass
                return tracing.current_ctx()

            ctx = pool.submit(tracing.bound(work)).result()
    inner = _by_name(_spans())["inner"][0]
    assert inner["trace"] == root.trace_id
    assert inner["parent"] == root.span_id
    assert ctx == (root.trace_id, root.span_id)


def test_header_roundtrip(traced):
    sp = tracing.begin("x")
    h = tracing.header_for(sp)
    assert h.startswith("00-") and h.endswith("-01")
    assert tracing.parse_header(h) == (sp.trace_id, sp.span_id)
    assert tracing.parse_header(None) is None
    assert tracing.parse_header("garbage") is None
    tracing.finish(sp)


def test_ring_is_bounded(traced):
    cap = tracing._ring.maxlen
    for i in range(cap + 50):
        tracing.finish(tracing.begin(f"s{i}"))
    spans = _spans()
    assert len(spans) == cap
    assert spans[0]["name"] == "s50"  # oldest evicted


def test_flag_off_every_entry_is_none(untraced):
    assert not tracing.enabled()
    assert tracing.begin("x") is None
    with tracing.span("y") as sp:
        assert sp is None
    assert tracing.bound(len) is len
    assert _spans() == []
    assert tracing.flight_dump("any") is None


# ---------------------------------------------------------------------------
# RPC plane propagation (real connections)
# ---------------------------------------------------------------------------


def test_one_trace_through_rpc_and_server(traced, server):
    conn = ps_server._Conn(server)
    with tracing.span("step_like") as root:
        assert conn.call("ping") == "pong"
    by = _by_name(_spans())
    rpc, att, srv = (by["rpc:ping"][0], by["attempt:ping"][0],
                     by["server:ping"][0])
    assert {rpc["trace"], att["trace"], srv["trace"]} == {root.trace_id}
    assert rpc["parent"] == root.span_id
    assert att["parent"] == rpc["span"]
    assert srv["parent"] == att["span"]  # reopened server-side
    conn.close()


def test_retry_spans_one_trace_with_backoff(traced, server, inject,
                                            monkeypatch):
    monkeypatch.setattr(ps_server, "RPC_BACKOFF_BASE", 0.01)
    inject("refuse:ping:1")
    conn = ps_server._Conn(server)
    with tracing.span("root") as root:
        assert conn.call("ping") == "pong"
    by = _by_name(_spans())
    attempts = by["attempt:ping"]
    assert len(attempts) == 2  # refused first send + the retry
    assert attempts[0]["status"].startswith("transport:")
    assert attempts[1]["status"] == "ok"
    assert by["backoff"], "backoff sleep must be its own span"
    assert {s["trace"] for s in attempts + by["backoff"]
            + by["server:ping"] + by["rpc:ping"]} == {root.trace_id}
    # the server span parents to the SECOND attempt (the one that landed)
    assert by["server:ping"][0]["parent"] == attempts[1]["span"]
    conn.close()


def test_replication_forward_joins_the_trace(traced, two_servers,
                                             monkeypatch):
    monkeypatch.setenv("PADDLE_PS_HEDGE_QUANTILE", "0")
    t = ps_server.RemoteTable("trace_repl", (64, 4), two_servers,
                              num_shards=2, learning_rate=0.5,
                              replication=2)
    tracing._reset_for_tests()  # drop the setup spans; keep the gate
    ids = np.arange(8, dtype=np.int64)
    grads = np.ones((8, 4), np.float32)
    with tracing.span("push_root") as root:
        t.push_gradients(ids, grads)
    by = _by_name(_spans())
    # client push -> primary handling -> replicate forward -> backup
    # handling: ONE trace end to end, parentage intact at every hop
    pushes = [s for s in by.get("server:push_gradients", ())
              if s["trace"] == root.trace_id]
    forwards_c = [s for s in by.get("rpc:replicate", ())
                  if s["trace"] == root.trace_id]
    forwards_s = [s for s in by.get("server:replicate", ())
                  if s["trace"] == root.trace_id]
    assert pushes and forwards_c and forwards_s
    push_ids = {s["span"] for s in pushes}
    for fc in forwards_c:
        assert fc["parent"] in push_ids  # forward issued while handling
    att_ids = {s["span"] for s in by.get("attempt:replicate", ())}
    for fs in forwards_s:
        assert fs["parent"] in att_ids
    # round/table identity rides the span attrs (tracetop's join keys)
    assert pushes[0]["attrs"]["table"] == "trace_repl"
    assert "round" in pushes[0]["attrs"]
    t.close()


def test_hedge_span_shares_the_trace(traced, two_servers, monkeypatch):
    monkeypatch.setenv("PADDLE_PS_HEDGE_QUANTILE", "0")
    t = ps_server.RemoteTable("trace_hedge", (64, 4), two_servers,
                              num_shards=2, replication=2)
    t._hedge_q = 0.95
    t._hedge_min = 4
    hist = get_registry().histogram("ps_client_rpc_ms", verb="gather")
    for _ in range(16):
        hist.observe(0.5)  # warm: hedge delay ~ sub-ms
    orig = t._replica_call

    def slow_primary(p, method, kwargs, hops=0):
        if method == "gather":
            time.sleep(0.25)  # force the hedge to win the race
        return orig(p, method, kwargs, hops)

    monkeypatch.setattr(t, "_replica_call", slow_primary)
    tracing._reset_for_tests()
    with tracing.span("gather_root") as root:
        out = t.gather(np.arange(4, dtype=np.int64))
    assert out.shape == (4, 4)
    time.sleep(0.3)  # let the losing primary future finish + record
    by = _by_name(_spans())
    hedges = [s for s in by.get("hedge:gather", ())]
    assert hedges, "hedge must record its own span"
    assert hedges[0]["trace"] == root.trace_id
    assert get_registry().counter("ps_client_hedges_issued_total",
                                  verb="gather").value >= 1
    t.close()


# ---------------------------------------------------------------------------
# flag-off bit-identity
# ---------------------------------------------------------------------------


def test_flag_off_wire_bytes_identical(untraced, server, monkeypatch):
    """With PADDLE_TRACING unset the payload the server receives is
    EXACTLY the caller's kwargs — no `_trace` key, no mutation — and no
    span is ever recorded."""
    seen = []
    orig = ps_server.PSServer.handle

    def spy(self, method, kwargs):
        seen.append((method, dict(kwargs)))
        return orig(self, method, kwargs)

    monkeypatch.setattr(ps_server.PSServer, "handle", spy)
    conn = ps_server._Conn(server)
    conn.call("create_table", spec={"name": "w", "shape": (8, 2)})
    conn.call("gather", name="w", ids=np.arange(3, dtype=np.int64))
    conn.close()
    assert seen and all("_trace" not in kw for _, kw in seen)
    assert _spans() == []


def test_flag_off_loss_trace_bit_identical(tmp_path):
    """The acceptance bit: an IN-PROCESS training run (dist_ps_worker
    standalone) produces a bitwise-identical loss trace with tracing on
    vs off — spans observe, never perturb."""
    def run(tag, env_extra):
        d = tmp_path / tag
        d.mkdir()
        env = dict(os.environ)
        env.pop("PADDLE_PSERVERS_IP_PORT_LIST", None)
        env.pop(tracing.ENV_GATE, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO
        env["PADDLE_DIST_TRACE_DIR"] = str(d)
        env["PS_TEST_STEPS"] = "6"
        env.update(env_extra)
        r = subprocess.run([sys.executable, "-u", WORKER], env=env,
                           capture_output=True, text=True, timeout=300,
                           cwd=REPO)
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        return json.load(open(d / "trace.0.json"))

    off = run("off", {})
    on = run("on", {tracing.ENV_GATE: "1"})
    assert on["losses"] == off["losses"]  # bitwise: json floats round-trip
    assert on["table_sum"] == off["table_sum"]


# ---------------------------------------------------------------------------
# executor step spans + the step-record join
# ---------------------------------------------------------------------------


def _tiny_train(steps=3):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4, 3], append_batch_size=False)
        y = layers.data("y", [4, 1], append_batch_size=False)
        loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
        fluid.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    xa = np.random.RandomState(0).rand(4, 3).astype(np.float32)
    ya = xa.sum(1, keepdims=True).astype(np.float32)
    for _ in range(steps):
        exe.run(main, feed={"x": xa, "y": ya}, fetch_list=[loss])


def test_step_span_children_and_record_join(traced, tmp_path,
                                            monkeypatch):
    from paddle_tpu.fluid import monitor
    from paddle_tpu.telemetry import sink

    path = tmp_path / "m.jsonl"
    monkeypatch.setenv(sink.ENV_PATH, str(path))
    sink.enable(str(path))
    monitor.reset_for_tests()
    try:
        _tiny_train(steps=2)
    finally:
        recs = [json.loads(l) for l in open(path)]
        sink.disable()
        monitor.reset_for_tests()
    steps = [r for r in recs if r["kind"] == "step"]
    assert steps, "no step records"
    by = _by_name(_spans())
    roots = by["step"]
    # every committed step record cites a REAL root span's trace
    trace_ids = {s["trace"] for s in roots}
    for r in steps:
        assert r["trace_id"] in trace_ids
    # breakdown children parent under the step root
    root_ids = {s["span"] for s in roots}
    # (the executor's phases, one RecordEvent each: fluid/profiler.py)
    for name in ("Executor::feed", "Executor::lookup", "Executor::state",
                 "Executor::dispatch", "Executor::commit",
                 "Executor::fetch"):
        assert by[name], f"missing {name} spans"
        assert all(s["parent"] in root_ids for s in by[name])
    # a cache-miss step records the compile under its look-up, and its
    # first call (tracing, lowering, XLA's compile) under another name
    assert by["Executor::compile"], "cache-miss step must record a compile span"
    lookups = {s["span"] for s in by["Executor::lookup"]}
    assert all(s["parent"] in lookups for s in by["Executor::compile"])
    assert by["Executor::compile"][0]["attrs"] == {"retrace": False}
    assert len(by["Executor::first_dispatch"]) == 2  # startup and main
    assert tracing.last_step_trace_id() in trace_ids


def test_tracez_slowest_first(traced):
    with tracing.span("fast"):
        pass
    with tracing.span("slow_trace"):
        time.sleep(0.05)
    z = tracing.tracez()
    assert z["enabled"] and len(z["traces"]) == 2
    assert z["traces"][0]["root"] == "slow_trace"
    assert z["traces"][0]["dur_ms"] >= z["traces"][1]["dur_ms"]
    assert z["traces"][0]["spans"][0]["dur_ms"] >= 50


def test_tracez_served_on_debugz(traced):
    import urllib.request

    from paddle_tpu.telemetry import debugz

    with tracing.span("served_span"):
        pass
    srv = debugz.serve(port=0, host="127.0.0.1")
    try:
        port = srv.server_address[1]
        z = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/tracez", timeout=5).read().decode())
        assert z["enabled"] is True
        assert any(t["root"] == "served_span" for t in z["traces"])
        idx = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=5).read().decode()
        assert "/tracez" in idx
    finally:
        debugz.stop()


# ---------------------------------------------------------------------------
# histogram exemplars
# ---------------------------------------------------------------------------


def test_histogram_exemplar_tracks_slowest_sample():
    from paddle_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", verb="gather")
    h.observe(5.0, trace_id="aaa")
    h.observe(900.0, trace_id="slowest")
    h.observe(20.0, trace_id="bbb")
    assert h.summary()["exemplar"]["trace_id"] == "slowest"
    text = reg.to_prometheus()
    assert '# {trace_id="slowest"} 900' in text
    # exactly one exemplar suffix, attached to the covering bucket line
    lines = [l for l in text.splitlines() if "# {trace_id=" in l]
    assert len(lines) == 1 and 'le="1000"' in lines[0]


def test_histogram_without_exemplar_unchanged():
    from paddle_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    h = reg.histogram("lat_ms")
    h.observe(5.0)
    assert "exemplar" not in h.summary()
    assert "# {" not in reg.to_prometheus()


def test_rpc_exemplar_lands_in_stats(traced, server):
    # fresh registry: the exemplar is a running max and earlier tests'
    # ping RPCs would otherwise keep theirs
    get_registry().reset()
    conn = ps_server._Conn(server)
    with tracing.span("er") as root:
        conn.call("ping")
    conn.close()
    h = get_registry().histogram("ps_client_rpc_ms", verb="ping")
    assert h.summary()["exemplar"]["trace_id"] == root.trace_id
    assert ps_server.client_telemetry(), "ps_client_* slice must exist"


# ---------------------------------------------------------------------------
# OTLP span export
# ---------------------------------------------------------------------------


def test_trace_export_otlp_shape_and_cursor(traced, monkeypatch):
    from paddle_tpu.telemetry import export

    posts = []

    class _Exp(export.PushExporter):
        def _post_once(self, body, ctype):
            posts.append((json.loads(body.decode()), ctype))

    with tracing.span("exported"):
        pass
    exp = _Exp("http://127.0.0.1:1/v1/traces", interval_s=3600,
               body_fn=export._traces_body_fn(), counter_prefix="traces")
    assert exp.flush() is True
    (payload, ctype), = posts
    assert ctype == "application/json"
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert any(s["name"] == "exported" for s in spans)
    sp = spans[-1]
    assert len(sp["traceId"]) == 32 and len(sp["spanId"]) == 16
    assert int(sp["endTimeUnixNano"]) >= int(sp["startTimeUnixNano"])
    # cursor advanced: nothing new -> no POST at all, still "delivered"
    assert exp.flush() is True
    assert len(posts) == 1
    exp.stop()


def test_trace_export_env_unset_zero_network(untraced, monkeypatch):
    from paddle_tpu.telemetry import export

    monkeypatch.delenv(export.ENV_TRACES_URL, raising=False)
    export.stop()
    assert export.maybe_start_traces() is None
    assert export.active_traces() is None
    export.stop()


# ---------------------------------------------------------------------------
# stall fault rule (the drill's deterministic tail)
# ---------------------------------------------------------------------------


def test_stall_rule_repeats_client_side():
    inj = faults.FaultInjector("stall:push_gradients:2:40")
    times = []
    for _ in range(4):
        t0 = time.perf_counter()
        inj.before_send("push_gradients")
        times.append(time.perf_counter() - t0)
    assert [t > 0.03 for t in times] == [False, True, False, True]
    with pytest.raises(ValueError):
        faults.parse_spec("stall:push_gradients:1")  # needs a duration


# ---------------------------------------------------------------------------
# tracetop: critical-path unit on a synthetic 3-process dump
# ---------------------------------------------------------------------------


def _write_synthetic_dumps(d):
    """Round 7 of table `emb` on pserver ps0: trainer0 arrives first and
    waits; trainer1 arrives 450ms later (client chain shows 1 retry) and
    releases the barrier; the apply forwards to ps1."""
    t0 = 1000.0

    def span(proc, name, sid, parent, ts, dur, trace="t" * 32, **attrs):
        s = {"trace": trace, "span": sid, "parent": parent, "name": name,
             "kind": "server" if name.startswith("server:") else "client",
             "ts": ts, "dur_ms": dur, "status": "ok", "proc": proc,
             "tid": 1}
        if attrs:
            s["attrs"] = attrs
        return s

    dumps = {
        "trainer0": [
            span("trainer0", "rpc:push_gradients", "c0", None,
                 t0, 462.0),
            span("trainer0", "attempt:push_gradients", "a0", "c0",
                 t0, 461.0, n=1),
        ],
        "trainer1": [
            span("trainer1", "rpc:push_gradients", "c1", None,
                 t0 + 0.01, 470.0),
            span("trainer1", "attempt:push_gradients", "a1x", "c1",
                 t0 + 0.01, 5.0, n=1),
            span("trainer1", "backoff", "b1", "c1", t0 + 0.02, 30.0),
            span("trainer1", "attempt:push_gradients", "a1", "c1",
                 t0 + 0.45, 20.0, n=2),
        ],
        "ps0": [
            span("ps0", "server:push_gradients", "s0", "a0",
                 t0 + 0.002, 455.0, verb="push_gradients", table="emb",
                 round=7, trainer=0),
            span("ps0", "barrier_wait", "w0", "s0", t0 + 0.004, 450.0,
                 table="emb", round=7, trainer=0),
            span("ps0", "server:push_gradients", "s1", "a1",
                 t0 + 0.452, 8.0, verb="push_gradients", table="emb",
                 round=7, trainer=1, released_round=7),
            span("ps0", "apply", "ap1", "s1", t0 + 0.453, 6.0,
                 table="emb", round=7, rows=32),
            span("ps0", "rpc:replicate", "f1", "ap1", t0 + 0.455, 3.0,
                 peer="127.0.0.1:9101"),
        ],
    }
    for proc, spans in dumps.items():
        with open(os.path.join(d, f"flightrec.{proc}.json"), "w") as f:
            json.dump({"format": 1, "process": proc, "pid": 1,
                       "reason": "exit", "ts": t0 + 1,
                       "spans": spans, "steps": []}, f)


def test_tracetop_critical_path_synthetic(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import tracetop

    _write_synthetic_dumps(str(tmp_path))
    dumps = tracetop.load_dumps(str(tmp_path))
    assert len(dumps) == 3
    spans = tracetop.merged_spans(dumps)
    rounds = tracetop.sync_rounds(spans)
    assert len(rounds) == 1
    r = rounds[0]
    assert (r["table"], r["round"], r["server"]) == ("emb", 7, "ps0")
    assert r["world"] == 2
    # culprit: trainer1's arrival released the barrier, 450ms after the
    # first arrival — the exact attribution the straggler path cites
    assert r["culprit"]["trainer"] == 1
    assert r["culprit"]["verb"] == "push_gradients"
    assert 440 <= r["culprit"]["critical_ms"] <= 460
    assert r["peer_wait_ms"] == 450.0
    releaser = [h for h in r["hops"] if h["released"]][0]
    assert releaser["attempts"] == 2  # client chain joined cross-process
    assert releaser["backoff_ms"] == 30.0
    assert releaser["client_ms"] == 470.0
    assert releaser["forwards"][0]["peer"] == "127.0.0.1:9101"
    text = tracetop.format_round(r)
    assert "released by trainer 1" in text and "push_gradients" in text
    # --json CLI round-trips
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tracetop.py"),
         str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["rounds"][0]["culprit"]["trainer"] == 1


def test_tracetop_empty_dir(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tracetop.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert out.returncode == 1
    assert "no flightrec" in out.stderr


# ---------------------------------------------------------------------------
# flight recorder (process layer)
# ---------------------------------------------------------------------------


def _run_script(body, tmp_path, env_extra=None, expect_rc=None,
                sig=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env[tracing.ENV_GATE] = "1"
    env[tracing.ENV_DIR] = str(tmp_path)
    env.update(env_extra or {})
    if sig is None:
        r = subprocess.run([sys.executable, "-u", "-c", body], env=env,
                           capture_output=True, text=True, timeout=120,
                           cwd=REPO)
        if expect_rc is not None:
            assert r.returncode == expect_rc, f"{r.stdout}\n{r.stderr}"
        return r
    p = subprocess.Popen([sys.executable, "-u", "-c", body], env=env,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, cwd=REPO)
    assert p.stdout.readline().strip() == "ready"
    p.send_signal(sig)
    p.wait(timeout=60)
    return p


@pytest.mark.slow
def test_flight_dump_on_injected_crash(tmp_path):
    """A `crash:` fault rule os._exit()s the process — atexit never
    runs, so the rule itself dumps the flight record first."""
    body = (
        "from paddle_tpu.telemetry import tracing\n"
        "from paddle_tpu.distributed import faults\n"
        "from paddle_tpu.fluid import flags as fl\n"
        "fl.set_flags({'FLAGS_ps_fault_injection': True})\n"
        "tracing.finish(tracing.begin('doomed_work'))\n"
        "faults.crash_point('myphase')\n"
    )
    r = _run_script(body, tmp_path, env_extra={
        faults.ENV_SPEC: "crash:myphase:1"}, expect_rc=1)
    # tag is pid-based for a bare python process: find it by glob
    recs = list(tmp_path.glob("flightrec.*.json"))
    assert recs, r.stdout
    rec = json.loads(recs[0].read_text())
    assert rec["reason"] == "crash:myphase"
    assert any(s["name"] == "doomed_work" for s in rec["spans"])


@pytest.mark.slow
def test_flight_dump_on_sigterm(tmp_path):
    body = (
        "import time\n"
        "from paddle_tpu.telemetry import tracing\n"
        "tracing.maybe_install_hooks()\n"
        "tracing.finish(tracing.begin('pre_sigterm_work'))\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n"
    )
    p = _run_script(body, tmp_path, sig=signal.SIGTERM)
    assert p.returncode != 0  # died OF the signal (dump then re-raise)
    recs = list(tmp_path.glob("flightrec.*.json"))
    assert recs
    rec = json.loads(recs[0].read_text())
    assert rec["reason"] == "sigterm"
    assert any(s["name"] == "pre_sigterm_work" for s in rec["spans"])
    # the chrome span lane for the timeline merge rides along
    assert list(tmp_path.glob("trace.*.json"))


@pytest.mark.slow
def test_flight_dump_on_bad_step(tmp_path):
    """BadStepError (FLAGS_check_numerics) dumps the step's spans
    BEFORE the raise unwinds — the bad step's trace is the evidence."""
    body = (
        "import numpy as np\n"
        "import paddle_tpu.fluid as fluid\n"
        "from paddle_tpu.fluid import layers, checkpoint\n"
        "from paddle_tpu.fluid import flags as fl\n"
        "fl.set_flags({'FLAGS_check_numerics': True})\n"
        "main, startup = fluid.Program(), fluid.Program()\n"
        "with fluid.program_guard(main, startup):\n"
        "    x = layers.data('x', [4, 3], append_batch_size=False)\n"
        "    y = layers.data('y', [4, 1], append_batch_size=False)\n"
        "    loss = layers.mean(layers.square_error_cost("
        "layers.fc(x, 1), y))\n"
        "    fluid.optimizer.SGDOptimizer(learning_rate=0.1)"
        ".minimize(loss)\n"
        "exe = fluid.Executor()\n"
        "exe.run(startup)\n"
        "bad = np.full((4, 3), np.nan, np.float32)\n"
        "ya = np.ones((4, 1), np.float32)\n"
        "try:\n"
        "    exe.run(main, feed={'x': bad, 'y': ya}, fetch_list=[loss])\n"
        "except checkpoint.BadStepError:\n"
        "    print('caught', flush=True)\n"
        "else:\n"
        "    raise SystemExit('guard did not fire')\n"
    )
    r = _run_script(body, tmp_path, expect_rc=0)
    assert "caught" in r.stdout
    recs = [p for p in tmp_path.glob("flightrec.*.json")]
    assert recs, r.stdout
    rec = json.loads(recs[0].read_text())
    # the bad_step dump fired; the atexit "exit" dump rewrote the file
    # with a superset ring and the accumulated reason list
    assert "bad_step" in rec["reasons"]
    names = {s["name"] for s in rec["spans"]}
    assert "data_wait" in names  # the step's children made it in


# ---------------------------------------------------------------------------
# the CI trace drill (acceptance)
# ---------------------------------------------------------------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_trace_drill_names_delayed_hop(tmp_path):
    """Acceptance: a 2-trainer + 1-pserver sync job with a deterministic
    400ms stall on trainer 1's push_gradients — the merged trace's
    per-round critical path must attribute >= 400ms to the
    (rank 1, push_gradients) hop, round after round; the whole-job
    timeline must gain pserver + coordinator lanes."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import tracetop

    trace_dir = tmp_path / "traces"
    losses_dir = tmp_path / "losses"
    losses_dir.mkdir()
    log_dir = tmp_path / "logs"
    env = dict(os.environ)
    env.pop("PADDLE_PSERVERS_IP_PORT_LIST", None)
    env.pop("PADDLE_TRAINERS_NUM", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = REPO
    env["PADDLE_DIST_TRACE_DIR"] = str(losses_dir)
    env["PS_TEST_STEPS"] = "6"
    env["FLAGS_ps_fault_injection"] = "1"
    env["PADDLE_PS_FAULT_SPEC"] = "stall:push_gradients:1:400"
    env["PADDLE_PS_FAULT_TAGS"] = "trainer1"
    # lease_secs 30: arms the coordinator (its renewal spans are the
    # "coord" lane we assert) with a startup grace far beyond the job's
    # wall time — the PS-only worker never renews a trainer lease, and
    # this drill is about tracing, not lease expiry
    r = subprocess.run(
        [sys.executable, "-u", "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--started_port", str(_free_port()),
         "--server_num", "1", "--log_dir", str(log_dir),
         "--trace_dir", str(trace_dir), "--lease_secs", "30",
         WORKER],
        env=env, capture_output=True, text=True, timeout=480, cwd=REPO)
    logs = ""
    if log_dir.exists():
        for pth in sorted(log_dir.iterdir()):
            if pth.is_file():
                logs += f"\n--- {pth.name} ---\n" + pth.read_text()[-2000:]
    assert r.returncode == 0, (
        f"drill failed rc={r.returncode}:\n{r.stdout}\n{r.stderr}\n{logs}")

    # flight dumps from every process class
    tags = {json.loads(p.read_text())["process"]
            for p in trace_dir.glob("flightrec.*.json")}
    assert {"trainer0", "trainer1", "ps0", "coord"} <= tags, tags

    # per-round critical path: the stalled rank is named, >= 400ms
    dumps = tracetop.load_dumps(str(trace_dir))
    rounds = tracetop.sync_rounds(tracetop.merged_spans(dumps),
                                  table="ps_dist_table")
    full = [r2 for r2 in rounds if r2["world"] == 2]
    assert len(full) >= 4, f"too few complete rounds: {rounds}"
    culprits = [(r2["culprit"]["trainer"], r2["culprit"]["verb"],
                 r2["culprit"]["critical_ms"]) for r2 in full]
    blamed_t1 = [c for c in culprits if str(c[0]) == "1"
                 and c[1] == "push_gradients"]
    assert len(blamed_t1) >= len(full) - 1, culprits  # warmup tolerance
    assert max(c[2] for c in blamed_t1) >= 400.0, culprits
    assert sorted(c[2] for c in blamed_t1)[len(blamed_t1) // 2] >= 350.0

    # the merged whole-job timeline gained pserver + coordinator lanes
    timeline_path = trace_dir / "timeline.json"
    assert timeline_path.exists()
    evs = json.loads(timeline_path.read_text())["traceEvents"]
    names = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert any("ps0" in n for n in names), names
    assert any("coordinator" in n for n in names), names

    # straggler-facing join: trainer step records carry trace_ids that
    # exist in the trainer's own span dump
    t1 = json.loads((trace_dir / "flightrec.trainer1.json").read_text())
    step_traces = {s["trace"] for s in t1["spans"]
                   if s["name"] == "step"}
    rec_traces = {rec.get("trace_id") for rec in t1["steps"]
                  if rec.get("trace_id")}
    assert rec_traces and rec_traces <= step_traces
