"""Chaos tests for the fault-tolerant PS data plane
(distributed/ps_server.py retry/dedup/recovery + distributed/faults.py).

The reference hardens its distributed runtime (grpc retry, heartbeat
timeouts, checkpoint recovery) but verifies it with luck; here every
fault is INJECTED on a deterministic schedule and the assertions are
exact:

  unit layer    — RPC retry/backoff survives dropped and refused
                  connections with EXACT numeric parity (a replayed
                  push applies once: the (trainer_id, step|seq) dedup
                  keys); a restarted server recovers its tables from
                  the latest atomic snapshot through the idempotent
                  create_table preload; a bumped generation resets the
                  sync barrier instead of deadlocking the new group
  process layer — (slow) a 2-trainer + 1-pserver launcher job trains to
                  the exact no-fault loss trace under injected
                  connection drops, and completes after a mid-run
                  pserver kill via supervised respawn + snapshot
                  recovery
"""
import os
import pickle
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed import faults, ps, ps_server
from paddle_tpu.fluid import flags as fl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_ps_worker.py")


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


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
def inject(monkeypatch):
    """Arm the fault layer with a spec; disarmed (and counters dropped)
    on teardown so no injection leaks into other tests."""

    def _arm(spec: str):
        monkeypatch.setenv(faults.ENV_SPEC, spec)
        fl.set_flags({"FLAGS_ps_fault_injection": True})
        faults.reset()

    yield _arm
    fl.set_flags({"FLAGS_ps_fault_injection": False})
    faults.reset()


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# fault layer itself
# ---------------------------------------------------------------------------


def test_fault_spec_parsing():
    rules = faults.parse_spec("drop:gather:3;delay:push_gradients:2:0.5; "
                              "refuse:*:1;kill:*:40")
    assert [(r.action, r.method, r.nth) for r in rules] == [
        ("drop", "gather", 3), ("delay", "push_gradients", 2),
        ("refuse", "*", 1), ("kill", "*", 40)]
    assert rules[1].arg == 0.5
    for bad in ("nonsense", "drop:gather", "boom:gather:1",
                "drop:gather:zero", "drop:gather:0"):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)


def test_rule_fires_exactly_once_on_nth_match():
    inj = faults.FaultInjector("refuse:gather:3")
    inj.before_send("gather")  # 1st: no fire
    inj.before_send("push_gradients")  # different verb: not counted
    inj.before_send("gather")  # 2nd
    with pytest.raises(faults.FaultError):
        inj.before_send("gather")  # 3rd: fires
    inj.before_send("gather")  # 4th: spent, never fires again


def test_injector_is_flag_gated(monkeypatch):
    monkeypatch.setenv(faults.ENV_SPEC, "drop:gather:1")
    fl.set_flags({"FLAGS_ps_fault_injection": False})
    faults.reset()
    assert faults.injector() is None  # spec set but flag off
    fl.set_flags({"FLAGS_ps_fault_injection": True})
    try:
        assert faults.injector() is not None
        monkeypatch.setenv(faults.ENV_SPEC, "")
        assert faults.injector() is None  # flag on but no spec
    finally:
        fl.set_flags({"FLAGS_ps_fault_injection": False})
        faults.reset()


# ---------------------------------------------------------------------------
# client retry / dedup (unit layer, in-thread server)
# ---------------------------------------------------------------------------


def test_gather_and_push_survive_faults_with_exact_parity(server, inject):
    """Dropped, refused, and delayed RPCs must be invisible: the hosted
    table stays bit-identical to the un-faulted local oracle. `drop`
    closes the connection after the request is sent (the server HAS
    applied the push: the retry must dedup); `refuse` never sends (the
    retry must apply)."""
    kw = dict(num_shards=4, optimizer="adagrad", learning_rate=0.3, seed=3)
    local = ps.ShardedHostTable("f1", (300, 8), **kw)
    remote = ps_server.RemoteTable("f1", (300, 8), [server], **kw)
    inject("drop:push_gradients:2;refuse:push_gradients:4;"
           "drop:gather:1;refuse:gather:3;delay:gather:2:0.05")

    rng = np.random.RandomState(0)
    for _ in range(6):
        ids = rng.randint(0, 300, (24,)).astype(np.int64)
        np.testing.assert_array_equal(remote.gather(ids), local.gather(ids))
        g = rng.randn(24, 8).astype(np.float32)
        remote.push_gradients(ids, g)
        local.push_gradients(ids, g)
    np.testing.assert_array_equal(remote.to_dense(), local.to_dense())
    # the dropped push reached the server AND its replay was skipped:
    # apply-once means exactly one push_call per client-side push
    assert remote.stats()["push_calls"] == 6
    remote.close()


def test_sync_barrier_push_replay_dedups(server, inject):
    """Sync mode: trainer 0's push connection is dropped after sending —
    the round merges with the ORIGINAL contribution and the replay must
    return without re-applying (round high-water), keeping exact parity
    with the single-process full-batch oracle."""
    kw = dict(num_shards=4, optimizer="sgd", learning_rate=0.2, seed=5)
    oracle = ps.ShardedHostTable("f2", (200, 8), **kw)
    t0 = ps_server.RemoteTable("f2", (200, 8), [server],
                               sync_trainers=2, trainer_id=0, **kw)
    t1 = ps_server.RemoteTable("f2", (200, 8), [server],
                               sync_trainers=2, trainer_id=1, **kw)
    inject("drop:push_gradients:1")

    rng = np.random.RandomState(1)
    for _ in range(3):
        ids = rng.randint(0, 200, (16,)).astype(np.int64)
        g = rng.randn(16, 8).astype(np.float32)
        errs = []

        def push(t, i, gg):
            try:
                t.push_gradients(i, gg)
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        th0 = threading.Thread(target=push, args=(t0, ids[:8], g[:8]))
        th1 = threading.Thread(target=push, args=(t1, ids[8:], g[8:]))
        th0.start(), th1.start()
        th0.join(30), th1.join(30)
        assert not errs, errs
        oracle.push_gradients(ids, g / 2.0)
        np.testing.assert_array_equal(t0.to_dense(), oracle.to_dense())
    t0.close(), t1.close()


def test_geo_delta_replay_dedups(server, inject):
    """push_delta is additive — a replayed delta would double-apply, so
    it carries a (trainer_id, seq) key the server dedups on retry."""
    kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5, seed=9)
    local = ps.ShardedHostTable("f3", (100, 4), **kw)
    remote = ps_server.RemoteTable("f3", (100, 4), [server], **kw)
    inject("drop:push_delta:1")
    ids = np.arange(20, dtype=np.int64)
    d = np.full((20, 4), 0.25, np.float32)
    remote.push_delta(ids, d)  # dropped reply -> replay -> apply ONCE
    local.push_delta(ids, d)
    remote.push_delta(ids, d)  # clean second push still applies
    local.push_delta(ids, d)
    np.testing.assert_array_equal(remote.to_dense(), local.to_dense())
    remote.close()


def test_stats_verb_reports_retry_counts_matching_drop_spec(server, inject):
    """Telemetry (ISSUE 4): the idempotent `stats` verb must account for
    exactly the faults the injected spec produced — 2 dropped push RPCs
    mean 2 client retries, 2 retry-marked arrivals and 2 replay-dedup
    hits server-side, and per-verb latency histograms that saw every
    RPC. In-thread server: client and server share the process registry,
    so counters are asserted as deltas."""
    from paddle_tpu import telemetry

    reg = telemetry.get_registry()

    def val(name, verb="push_gradients"):
        return reg.counter(name, verb=verb).value

    before = {n: val(n) for n in (
        "ps_client_retries_total", "ps_server_retry_received_total",
        "ps_server_replay_dedup_total", "ps_client_rpc_total",
        "ps_server_rpc_total")}
    kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.2, seed=5)
    remote = ps_server.RemoteTable("f_stats", (100, 4), [server], **kw)
    inject("drop:push_gradients:2;drop:push_gradients:4")
    rng = np.random.RandomState(1)
    for _ in range(5):
        ids = rng.randint(0, 100, (10,)).astype(np.int64)
        remote.push_gradients(ids, rng.randn(10, 4).astype(np.float32))
    st = remote.stats()
    # table-level traffic: apply-once despite the two drops
    assert st["push_calls"] == 5
    # client side: one retry attempt per dropped RPC, successes count 5
    assert val("ps_client_retries_total") - before[
        "ps_client_retries_total"] == 2
    assert val("ps_client_rpc_total") - before["ps_client_rpc_total"] == 5
    # server side, via the stats verb payload: both replays arrived
    # marked and were deduped (the first sends had landed)
    (tele,) = st["servers"]

    def server_val(name, verb="push_gradients"):
        for row in tele.get(name, {}).get("series", []):
            if row["labels"].get("verb") == verb:
                return row["value"]
        return 0

    assert server_val("ps_server_retry_received_total") - _srv_before(
        before, "ps_server_retry_received_total") == 2
    assert server_val("ps_server_replay_dedup_total") - _srv_before(
        before, "ps_server_replay_dedup_total") == 2
    # the server handled 5 first sends + 2 replays of push_gradients
    assert server_val("ps_server_rpc_total") - _srv_before(
        before, "ps_server_rpc_total") == 7
    # latency histograms exist for the verbs that ran
    lat = tele.get("ps_server_rpc_ms", {}).get("series", [])
    assert any(r["labels"].get("verb") == "push_gradients" and r["count"]
               for r in lat)
    remote.close()


def _srv_before(before, name):
    return before[name]


def test_retry_exhaustion_raises_connection_error(monkeypatch):
    monkeypatch.setattr(ps_server, "RPC_MAX_RETRIES", 2)
    monkeypatch.setattr(ps_server, "RPC_BACKOFF_BASE", 0.01)
    conn = ps_server._Conn(f"127.0.0.1:{_free_port()}")  # nobody listens
    t0 = time.time()
    with pytest.raises(ConnectionError, match="after 3 attempts"):
        conn.call("ping")
    assert time.time() - t0 < 10


# ---------------------------------------------------------------------------
# snapshot recovery + generation reset (unit layer)
# ---------------------------------------------------------------------------


def test_pserver_restart_recovers_table_from_snapshot(tmp_path):
    """The full recovery story without processes: server A snapshots,
    dies; server B comes up on the SAME port preloading the snapshot
    dir; the client's next RPC rides the retry loop through the outage,
    hits TableMissingError, re-creates (idempotent), and reads back the
    pre-crash state."""
    snap = str(tmp_path / "snaps")
    port = _free_port()

    def run_server(preload):
        ready = threading.Event()
        t = threading.Thread(
            target=ps_server.serve,
            args=(port, "127.0.0.1", lambda a: ready.set()),
            kwargs=dict(preload_dir=preload, snapshot_dir=snap,
                        snapshot_secs=0.0),
            daemon=True)
        t.start()
        assert ready.wait(10)
        return t

    ta = run_server(preload=None)
    ep = f"127.0.0.1:{port}"
    kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5, seed=4)
    oracle = ps.ShardedHostTable("f4", (80, 4), **kw)
    remote = ps_server.RemoteTable("f4", (80, 4), [ep], **kw)
    ids = np.arange(40, dtype=np.int64)
    g = np.ones((40, 4), np.float32)
    remote.push_gradients(ids, g)
    oracle.push_gradients(ids, g)
    assert ps_server._Conn(ep).call("snapshot") == 1  # on-demand snapshot
    with open(os.path.join(snap, "f4.pkl"), "rb") as f:
        pickle.load(f)  # loadable, and no torn tmp files left behind
    assert not [p for p in os.listdir(snap) if ".tmp" in p]

    ps_server._Conn(ep).call("shutdown")
    ta.join(timeout=10)
    tb = run_server(preload=snap)  # "supervised respawn" on the same port
    # same client object: retry -> reconnect -> recreate -> snapshot state
    np.testing.assert_array_equal(remote.to_dense(), oracle.to_dense())
    remote.push_gradients(ids, g)  # and it keeps training
    oracle.push_gradients(ids, g)
    np.testing.assert_array_equal(remote.to_dense(), oracle.to_dense())
    remote.close()
    ps_server._Conn(ep).call("shutdown")
    tb.join(timeout=10)


def test_generation_bump_resets_stale_sync_round(server, monkeypatch):
    """A trainer group dies leaving a half-filled sync round; the
    restarted group (bumped generation in the create handshake) must
    never inherit it: the stale waiter is woken to FAIL FAST (not after
    SYNC_TIMEOUT) and the new group's rounds merge cleanly from step 0."""
    monkeypatch.setattr(ps_server, "SYNC_TIMEOUT", 60.0)
    kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5, seed=7)
    dead = ps_server.RemoteTable("f5", (60, 4), [server], sync_trainers=2,
                                 trainer_id=0, generation=0, **kw)
    errs = []

    def stale_push():
        try:
            dead.push_gradients(np.arange(4, dtype=np.int64),
                                np.ones((4, 4), np.float32))
        except RuntimeError as e:
            errs.append(e)

    th = threading.Thread(target=stale_push, daemon=True)
    th.start()
    time.sleep(0.3)  # let the push park in the barrier

    # "restarted group": same table, generation 1 — resets the barrier
    t0 = ps_server.RemoteTable("f5", (60, 4), [server], sync_trainers=2,
                               trainer_id=0, generation=1, **kw)
    t1 = ps_server.RemoteTable("f5", (60, 4), [server], sync_trainers=2,
                               trainer_id=1, generation=1, **kw)
    th.join(timeout=10)  # woken by the reset, NOT by the 60s timeout
    assert not th.is_alive(), "stale waiter still parked after reset"
    assert errs and "abandoned" in str(errs[0])

    oracle = ps.ShardedHostTable("f5o", (60, 4), **kw)
    ids = np.arange(8, dtype=np.int64)
    g = np.ones((8, 4), np.float32)
    ths = [threading.Thread(target=t.push_gradients, args=(ids[i::2], g[i::2]))
           for i, t in enumerate((t0, t1))]
    [t.start() for t in ths]
    [t.join(30) for t in ths]
    oracle.push_gradients(ids, g / 2.0)
    np.testing.assert_array_equal(t0.to_dense(), oracle.to_dense())
    dead.close(), t0.close(), t1.close()


# ---------------------------------------------------------------------------
# process layer (launcher end to end) — slow: full chaos drills
# ---------------------------------------------------------------------------


def _env(tmpdir, extra=None):
    env = dict(os.environ)
    env.pop("PADDLE_PSERVERS_IP_PORT_LIST", None)
    env.pop("PADDLE_TRAINERS_NUM", None)
    env.pop("PADDLE_PS_FAULT_SPEC", None)
    env.pop("FLAGS_ps_fault_injection", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PADDLE_DIST_TRACE_DIR"] = str(tmpdir)
    env["PYTHONPATH"] = REPO
    env.update(extra or {})
    return env


def _launch_ps_job(tmp_path, extra_env=None, extra_args=(), timeout=480):
    dist_dir = tmp_path / "dist"
    dist_dir.mkdir(exist_ok=True)
    log_dir = tmp_path / "logs"
    r = subprocess.run(
        [sys.executable, "-u", "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--started_port", str(_free_port()),
         "--server_num", "1", "--log_dir", str(log_dir),
         *extra_args, WORKER],
        env=_env(dist_dir, extra_env), capture_output=True, text=True,
        timeout=timeout, cwd=REPO)
    logs = ""
    if log_dir.exists():
        for pth in sorted(log_dir.iterdir()):
            if pth.is_file():
                logs += f"\n--- {pth.name} ---\n" + pth.read_text()[-3000:]
    return r, logs


@pytest.mark.slow
def test_chaos_connection_drops_match_no_fault_loss(tmp_path):
    """Acceptance (a): with deterministic connection drops, refusals and
    delays injected into every trainer's RPC client, training converges
    to the EXACT no-fault result — retries + dedup make transport faults
    invisible to the math."""
    import json

    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    r = subprocess.run([sys.executable, "-u", WORKER],
                       env=_env(ref_dir), capture_output=True, text=True,
                       timeout=300, cwd=REPO)
    assert r.returncode == 0, f"single run failed:\n{r.stdout}\n{r.stderr}"
    ref = json.load(open(ref_dir / "trace.0.json"))

    dist_dir = tmp_path / "dist"
    r, logs = _launch_ps_job(tmp_path, {
        "FLAGS_ps_fault_injection": "1",
        "PADDLE_PS_FAULT_SPEC": ("drop:push_gradients:3;"
                                 "refuse:push_gradients:7;"
                                 "drop:gather:2;refuse:gather:5;"
                                 "delay:push_gradients:9:0.2"),
    })
    assert r.returncode == 0, (
        f"chaos job failed rc={r.returncode}:\n{r.stdout}\n{r.stderr}\n{logs}")
    t0 = json.load(open(dist_dir / "trace.0.json"))
    t1 = json.load(open(dist_dir / "trace.1.json"))
    avg = (np.asarray(t0["losses"]) + np.asarray(t1["losses"])) / 2.0
    np.testing.assert_allclose(avg, ref["losses"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(t0["table_sum"], ref["table_sum"], rtol=1e-5)


# ---------------------------------------------------------------------------
# RPC deadline + replication fault rules (ISSUE 7)
# ---------------------------------------------------------------------------


def test_call_deadline_bounds_wall_time_not_attempts(monkeypatch):
    """PADDLE_PS_CALL_DEADLINE_SECS: with a deadline set, the retry loop
    gives up at the DEADLINE even though the attempt budget is nowhere
    near spent — the property failover latency depends on."""
    monkeypatch.setattr(ps_server, "RPC_MAX_RETRIES", 10_000_000)
    monkeypatch.setattr(ps_server, "RPC_BACKOFF_BASE", 0.01)
    conn = ps_server._Conn(f"127.0.0.1:{_free_port()}", deadline=0.5)
    t0 = time.time()
    with pytest.raises(ConnectionError, match="deadline"):
        conn.call("ping")
    elapsed = time.time() - t0
    assert elapsed < 3.0, f"deadline did not bound wall time: {elapsed}s"


def test_call_deadline_off_keeps_attempt_bound(monkeypatch):
    """Deadline unset (the R=1 default): exactly the old attempt-count
    behavior, same terminal message."""
    monkeypatch.setattr(ps_server, "RPC_MAX_RETRIES", 2)
    monkeypatch.setattr(ps_server, "RPC_BACKOFF_BASE", 0.01)
    conn = ps_server._Conn(f"127.0.0.1:{_free_port()}", deadline=0)
    with pytest.raises(ConnectionError, match="after 3 attempts"):
        conn.call("ping")


def test_slow_rule_fires_every_nth():
    """`slow` is REPEATING: every nth matching call sleeps arg ms —
    a deterministic latency tail, not a one-shot."""
    inj = faults.FaultInjector("slow:gather:2:30")
    times = []
    for _ in range(6):
        t0 = time.perf_counter()
        inj.on_server_call("gather")
        times.append(time.perf_counter() - t0)
    slow = [t > 0.02 for t in times]
    assert slow == [False, True, False, True, False, True], times
    inj.on_server_call("push_gradients")  # other verbs unaffected


def test_partition_rule_latches_and_blocks_replication(monkeypatch):
    """`partition:<tag>:<nth>`: after this server handles nth RPCs it
    latches into a reachable-but-stale state — blocks_replication()
    stays True — and only fires on the server whose tag matches."""
    monkeypatch.setenv("PADDLE_PS_RANK_TAG", "ps1")
    inj = faults.FaultInjector("partition:ps1:3")
    for _ in range(2):
        inj.on_server_call("gather")
        assert not inj.blocks_replication()
    inj.on_server_call("push_gradients")
    assert inj.blocks_replication()
    inj.on_server_call("gather")
    assert inj.blocks_replication()  # latched
    # a different tag never fires
    monkeypatch.setenv("PADDLE_PS_RANK_TAG", "ps0")
    inj2 = faults.FaultInjector("partition:ps1:1")
    inj2.on_server_call("gather")
    assert not inj2.blocks_replication()


def test_fault_tags_scope_the_injector(monkeypatch):
    """PADDLE_PS_FAULT_TAGS arms the layer only in the named processes
    (kill ONE replica of a pair instead of both)."""
    monkeypatch.setenv(faults.ENV_SPEC, "drop:gather:1")
    monkeypatch.setenv(faults.ENV_TAGS, "ps0")
    fl.set_flags({"FLAGS_ps_fault_injection": True})
    try:
        monkeypatch.setenv("PADDLE_PS_RANK_TAG", "ps1")
        faults.reset()
        assert faults.injector() is None  # not my tag
        monkeypatch.setenv("PADDLE_PS_RANK_TAG", "ps0")
        faults.reset()
        assert faults.injector() is not None
        monkeypatch.delenv("PADDLE_PS_RANK_TAG")
        monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
        monkeypatch.setenv(faults.ENV_TAGS, "trainer1")
        faults.reset()
        assert faults.injector() is not None  # trainer tags work too
    finally:
        fl.set_flags({"FLAGS_ps_fault_injection": False})
        faults.reset()


def test_stale_epoch_write_from_deposed_primary_rejected():
    """The seq/epoch fence (ISSUE 7 satellite): a deposed primary's
    forwarded write — stale generation — is REJECTED by the backup's
    epoch check, and the deposed server latches stale so clients
    re-route instead of reading a diverged copy."""
    srv = ps_server.PSServer()
    key = "d@p0"
    spec = {"name": "d", "shape": (20, 4), "num_shards": 2,
            "optimizer": "sgd", "learning_rate": 0.5, "seed": 1,
            "partition": 0, "replicas": []}
    srv.create_table(dict(spec))
    ids = np.arange(4, dtype=np.int64)
    g = np.ones((4, 4), np.float32)
    # the replica is promoted at epoch 2 (a failover happened)
    srv.promote(key, epoch=2, backups=[])
    before = srv.tables[key].to_dense().copy()
    # a deposed primary still forwarding at epoch 1 must bounce
    with pytest.raises(RuntimeError, match="StaleEpoch"):
        srv.replicate(key, epoch=1, seq=1, op="push_gradients",
                      ids=ids, payload=g)
    np.testing.assert_array_equal(srv.tables[key].to_dense(), before)
    # a CURRENT-epoch forward with a stale seq is acked-not-reapplied
    srv.replicas[key].role = "backup"
    srv.replicas[key].seq = 5
    out = srv.replicate(key, epoch=2, seq=3, op="push_gradients",
                        ids=ids, payload=g)
    assert out == {"seq": 5}
    np.testing.assert_array_equal(srv.tables[key].to_dense(), before)
    # and a seq GAP demands resync instead of silently applying
    with pytest.raises(RuntimeError, match="ReplicaGap"):
        srv.replicate(key, epoch=2, seq=9, op="push_gradients",
                      ids=ids, payload=g)


def test_deposed_primary_refuses_clients_until_resync():
    """Once a primary learns it was deposed (stale latch), client verbs
    bounce with StalePrimaryError — no reads of a diverged copy."""
    srv = ps_server.PSServer()
    key = "d2@p0"
    spec = {"name": "d2", "shape": (20, 4), "num_shards": 2,
            "optimizer": "sgd", "learning_rate": 0.5, "seed": 1,
            "partition": 0, "replicas": []}
    srv.create_table(dict(spec))
    srv.promote(key, epoch=0, backups=[])
    srv.replicas[key].stale = True  # deposed (forward was epoch-rejected)
    with pytest.raises(ps_server.StalePrimaryError):
        srv.push_gradients("d2", np.arange(2, dtype=np.int64),
                           np.ones((2, 4), np.float32), partition=0)
    with pytest.raises(ps_server.StalePrimaryError):
        srv.gather("d2", np.arange(2, dtype=np.int64), partition=0)


@pytest.mark.slow
def test_chaos_pserver_kill_recovers_from_snapshot(tmp_path):
    """Acceptance (b): the pserver is killed mid-run (deterministic kill
    rule); the launcher's supervisor respawns it on the same port
    preloading the latest snapshot, the trainers' clients reconnect and
    re-create the table, and the job COMPLETES — at most one snapshot
    interval of updates lost (Downpour bounded staleness), not the job."""
    import json

    dist_dir = tmp_path / "dist"
    r, logs = _launch_ps_job(
        tmp_path,
        {"FLAGS_ps_fault_injection": "1",
         "PADDLE_PS_FAULT_SPEC": "kill:*:40",
         "PADDLE_PS_SNAPSHOT_SECS": "0.3"},
        extra_args=("--elastic_retries", "1"), timeout=480)
    assert "restarting it on the same port" in r.stderr, (
        f"no pserver respawn seen:\n{r.stderr}\n{logs}")
    assert r.returncode == 0, (
        f"job failed rc={r.returncode}:\n{r.stdout}\n{r.stderr}\n{logs}")
    t0 = json.load(open(dist_dir / "trace.0.json"))
    t1 = json.load(open(dist_dir / "trace.1.json"))
    assert np.isfinite(t0["losses"]).all() and np.isfinite(t1["losses"]).all()
    # both ranks still observe ONE shared (recovered) table at the end
    np.testing.assert_allclose(t0["table_sum"], t1["table_sum"], rtol=0)
