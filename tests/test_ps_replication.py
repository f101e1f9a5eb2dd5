"""Replicated PS tables (distributed/ps_server.py, ISSUE 7): fast
failover, hedged reads, incremental snapshots.

Unit layer (in-thread servers, hard-killable):
  - R replicas of a partition initialize and stay BIT-identical: the
    primary forwards every applied write with a per-partition apply seq
  - killing a primary promotes the next live replica and training
    CONTINUES with exact parity — no respawn wait
  - a respawned replica catches up via anti-entropy (seq-tail replay
    when the primary's write ring covers it, full state otherwise) and
    rejoins as backup
  - read verbs hedge to a backup after the observed latency quantile;
    first response wins and the counters account for it
  - incremental snapshots write O(touched rows) per tick, chain-restore
    to exactly the full-snapshot state, and compact

Process layer (@slow, launcher drills):
  - R=2 kill-primary: the loss trace is bit-identical to the no-fault
    run of the same topology
  - injected server-side tail: hedges win and the pull p95 recovers
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu import telemetry
from paddle_tpu.distributed import faults, ps, ps_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_ps_worker.py")
_REG = telemetry.get_registry()


# ---------------------------------------------------------------------------
# in-thread server harness (hard-killable, same-port respawn)
# ---------------------------------------------------------------------------


class _Srv:
    def __init__(self, port=0, preload=None, snapdir=None, mode=None):
        self.ready = threading.Event()
        self.kw = dict(preload_dir=preload, snapshot_dir=snapdir,
                       snapshot_mode=mode)
        self.srv = None
        self.thread = threading.Thread(target=self._run, args=(port,),
                                       daemon=True)
        self.thread.start()
        assert self.ready.wait(10)

    def _run(self, port):
        self.srv = ps_server._TCPServer(("127.0.0.1", port),
                                        ps_server._Handler)
        self.srv.ps = ps_server.PSServer(**self.kw)
        self.ep = f"127.0.0.1:{self.srv.server_address[1]}"
        self.port = self.srv.server_address[1]
        self.ready.set()
        self.srv.serve_forever(poll_interval=0.05)

    def kill(self):
        """Abrupt death: listener closed AND every live connection
        reset, so clients see exactly what a crashed process gives."""
        self.srv.shutdown()
        self.srv.close_all_connections()
        self.srv.server_close()
        self.thread.join(timeout=5)

    @property
    def ps(self):
        return self.srv.ps


@pytest.fixture
def fast_failover(monkeypatch):
    """Bound failover detection to ~1s so the tests stay fast; shrink
    the rejoin window so give-up paths cannot linger across tests."""
    monkeypatch.setattr(ps_server, "REPLICATED_DEADLINE_DEFAULT", 1.0)
    monkeypatch.setattr(ps_server, "REJOIN_SECS", 30.0)


def _mk_oracle(rows, dim, n_parts, **kw):
    """Per-partition local oracles with the replicated seed layout
    (partition p seeded seed+p, rows r%n at local r//n)."""
    seed = kw.pop("seed")
    parts = [
        ps.ShardedHostTable(
            f"oracle{p}", ((rows - p + n_parts - 1) // n_parts, dim),
            seed=seed + p, **kw)
        for p in range(n_parts)
    ]

    class O:
        def gather(self, ids):
            ids = np.asarray(ids, np.int64)
            out = np.empty((len(ids), dim), np.float32)
            for p in range(n_parts):
                m = ids % n_parts == p
                if m.any():
                    out[m] = parts[p].gather(ids[m] // n_parts)
            return out

        def push_gradients(self, ids, g):
            ids = np.asarray(ids, np.int64)
            for p in range(n_parts):
                m = ids % n_parts == p
                if m.any():
                    parts[p].push_gradients(ids[m] // n_parts, g[m])

    return O()


# ---------------------------------------------------------------------------
# replication basics
# ---------------------------------------------------------------------------


def test_replication_requires_enough_pservers():
    a = _Srv()
    try:
        with pytest.raises(ValueError, match="replication=2"):
            ps_server.RemoteTable("rv", (10, 4), [a.ep], replication=2)
    finally:
        a.kill()


def test_r1_wire_format_and_files_unchanged(tmp_path):
    """The R=1 default must stay byte-compatible: no partition/replicas
    keys in the create spec, zero replication verbs on the wire, and
    snapshot files named exactly <name>.pkl with a plain state_dict."""
    a = _Srv(snapdir=str(tmp_path))
    try:
        before = _REG.counter("ps_server_rpc_total", verb="promote").value
        kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5, seed=1)
        t = ps_server.RemoteTable("plain", (40, 4), [a.ep], **kw)
        spec = a.ps.specs["plain"]
        assert "partition" not in spec and "replicas" not in spec
        assert "plain" in a.ps.tables  # bare-name key
        assert a.ps.replicas == {}  # no replica state at R=1
        t.push_gradients(np.arange(4, dtype=np.int64),
                         np.ones((4, 4), np.float32))
        assert a.ps.snapshot() == 1
        import pickle

        state = pickle.load(open(tmp_path / "plain.pkl", "rb"))
        assert "replica_meta" not in state and "shards" in state
        assert _REG.counter("ps_server_rpc_total",
                            verb="promote").value == before
        t.close()
    finally:
        a.kill()


def test_replicated_parity_and_backup_prefix_consistency(fast_failover):
    """Every write the client sees acked is on EVERY replica: gathers
    match the local oracle, a direct backup-side read returns the same
    rows as the primary, and replica seq lag is zero at rest."""
    a, b, c = _Srv(), _Srv(), _Srv()
    try:
        kw = dict(num_shards=2, optimizer="adagrad", learning_rate=0.3,
                  seed=3)
        remote = ps_server.RemoteTable("r3", (90, 8), [a.ep, b.ep, c.ep],
                                       replication=2, **kw)
        oracle = _mk_oracle(90, 8, 3, **dict(kw))
        rng = np.random.RandomState(0)
        for _ in range(5):
            ids = rng.randint(0, 90, (24,)).astype(np.int64)
            np.testing.assert_array_equal(remote.gather(ids),
                                          oracle.gather(ids))
            g = rng.randn(24, 8).astype(np.float32)
            remote.push_gradients(ids, g)
            oracle.push_gradients(ids, g)
        # partition 0: primary on a, backup on b — compare their copies
        prim = a.ps.tables["r3@p0"].to_dense()
        back = b.ps.tables["r3@p0"].to_dense()
        np.testing.assert_array_equal(prim, back)
        st = remote.replica_status()
        assert [r["replicas"][0]["role"] for r in st] == ["primary"] * 3
        assert [r["replicas"][1]["role"] for r in st] == ["backup"] * 3
        assert all(r["max_lag"] == 0 for r in st), st
        # stats() surfaces the replication section for operators
        agg = remote.stats()
        assert agg["replication"]["factor"] == 2
        assert len(agg["replication"]["partitions"]) == 3
        remote.close()
    finally:
        for s in (a, b, c):
            s.kill()


def test_failover_promotes_backup_and_training_continues(fast_failover):
    """Kill the primary of partition 0 mid-run: the client promotes the
    backup within its deadline budget and the continued training stays
    BIT-identical to the oracle — the no-stall acceptance property."""
    a, b = _Srv(), _Srv()
    try:
        kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5, seed=4)
        remote = ps_server.RemoteTable("r4", (100, 4), [a.ep, b.ep],
                                       replication=2, **kw)
        oracle = _mk_oracle(100, 4, 2, **dict(kw))
        rng = np.random.RandomState(1)
        for _ in range(4):
            ids = rng.randint(0, 100, (16,)).astype(np.int64)
            g = rng.randn(16, 4).astype(np.float32)
            remote.push_gradients(ids, g)
            oracle.push_gradients(ids, g)
        failovers0 = _REG.counter("ps_client_failovers_total").value
        a.kill()  # partition 0's primary, partition 1's backup
        t0 = time.time()
        for _ in range(4):
            ids = rng.randint(0, 100, (16,)).astype(np.int64)
            g = rng.randn(16, 4).astype(np.float32)
            remote.push_gradients(ids, g)
            oracle.push_gradients(ids, g)
            np.testing.assert_array_equal(remote.gather(ids),
                                          oracle.gather(ids))
        # bounded by the 1s deadline + promote, not a respawn wait
        assert time.time() - t0 < 20
        assert _REG.counter("ps_client_failovers_total").value > failovers0
        np.testing.assert_array_equal(
            remote.gather(np.arange(100, dtype=np.int64)),
            oracle.gather(np.arange(100, dtype=np.int64)))
        st = remote.replica_status()
        surv = [r for r in st[0]["replicas"] if "error" not in r]
        assert [r["role"] for r in surv] == ["primary"]
        assert st[0]["epoch"] >= 1
        remote.close()
    finally:
        for s in (a, b):
            try:
                s.kill()
            except Exception:
                pass


def test_respawn_catches_up_then_rejoins_as_backup(fast_failover):
    """After failover, a server respawned on the same port is re-created
    by the client's rejoin thread, pulls the seq tail from the current
    primary (anti-entropy), and rejoins as a zero-lag backup that keeps
    receiving forwards."""
    a, b = _Srv(), _Srv()
    try:
        kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5, seed=4)
        remote = ps_server.RemoteTable("r5", (100, 4), [a.ep, b.ep],
                                       replication=2, **kw)
        oracle = _mk_oracle(100, 4, 2, **dict(kw))
        rng = np.random.RandomState(2)

        def push(n):
            for _ in range(n):
                ids = rng.randint(0, 100, (16,)).astype(np.int64)
                g = rng.randn(16, 4).astype(np.float32)
                remote.push_gradients(ids, g)
                oracle.push_gradients(ids, g)

        push(3)
        port_a = a.port
        a.kill()
        push(3)  # fails over; rejoin threads start probing port_a
        a2 = _Srv(port=port_a)
        deadline = time.time() + 30
        while time.time() < deadline:
            st = remote.replica_status()
            roles = {r["endpoint"]: r.get("role")
                     for r in st[0]["replicas"]}
            if (roles.get(f"127.0.0.1:{port_a}") == "backup"
                    and all(r.get("max_lag") == 0 for r in st)):
                break
            time.sleep(0.3)
        else:
            pytest.fail(f"respawned pserver never rejoined: {st}")
        push(2)  # forwards now include the rejoined backup
        np.testing.assert_array_equal(
            remote.gather(np.arange(100, dtype=np.int64)),
            oracle.gather(np.arange(100, dtype=np.int64)))
        # the rejoined backup's copy is the primary's copy, bit for bit
        np.testing.assert_array_equal(a2.ps.tables["r5@p0"].to_dense(),
                                      b.ps.tables["r5@p0"].to_dense())
        assert all(r["max_lag"] == 0 for r in remote.replica_status())
        remote.close()
        a2.kill()
    finally:
        for s in (a, b):
            try:
                s.kill()
            except Exception:
                pass


def test_fetch_replica_state_tail_vs_full():
    """Anti-entropy chooses the cheap path: a requester whose have_seq
    is covered by the primary's write ring gets only the tail; one too
    far behind (or fresh) gets a full state transfer."""
    srv = ps_server.PSServer()
    spec = {"name": "t", "shape": (20, 4), "num_shards": 2,
            "optimizer": "sgd", "learning_rate": 0.5, "seed": 1,
            "partition": 0, "replicas": []}
    srv.create_table(dict(spec))
    key = "t@p0"
    srv.promote(key, epoch=0, backups=[])
    for i in range(5):
        srv.push_gradients("t", np.arange(4, dtype=np.int64),
                           np.ones((4, 4), np.float32), partition=0)
    assert srv.replicas[key].seq == 5
    out = srv.fetch_replica_state(key, have_seq=3)
    assert "tail" in out and [e[0] for e in out["tail"]] == [4, 5]
    assert out["seq"] == 5
    out = srv.fetch_replica_state(key, have_seq=5)
    assert out["tail"] == []
    # uncovered: force the ring to forget the early seqs
    srv.replicas[key].log = type(srv.replicas[key].log)(
        list(srv.replicas[key].log)[-1:], maxlen=4)
    out = srv.fetch_replica_state(key, have_seq=1)
    assert "state" in out and "tail" not in out
    # have_seq < 0 is the stale replica's explicit full-transfer demand:
    # its local seq counts writes the cluster never accepted, so even a
    # ring-covered value must not be trusted
    out = srv.fetch_replica_state(key, have_seq=-1)
    assert "state" in out and "tail" not in out


def test_launch_validates_replication_against_endpoint_count(tmp_path):
    """--ps_replication R must fail AT LAUNCH when fewer than R pserver
    endpoints are supplied — whether counted from --server_num or an
    explicit --servers list — instead of surfacing later as a
    RemoteTable ValueError inside every trainer."""
    script = tmp_path / "noop.py"
    script.write_text("pass\n")
    for extra in (["--server_num", "1"], ["--servers", "127.0.0.1:1"]):
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             *extra, "--ps_replication", "2", str(script)],
            capture_output=True, text=True, timeout=60)
        assert r.returncode == 2, (r.stdout, r.stderr)
        assert "needs at least that many pservers" in r.stderr


def test_deposed_primary_divergence_forces_full_resync(fast_failover):
    """Regression: a primary that applied a client write BEFORE its
    forward was epoch-rejected (deposed mid-failover race) holds a
    divergent row under a seq that matches the new primary's — same
    number, different content. Anti-entropy must not trust that seq
    ('covered' would hand back an empty tail and the replica would
    rejoin 'clean' while still divergent): the stale replica demands a
    FULL state transfer and comes back bit-identical."""
    a, b = _Srv(), _Srv()
    try:
        kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5,
                  seed=11)
        remote = ps_server.RemoteTable("dv", (20, 4), [a.ep, b.ep],
                                       replication=2, **kw)
        ids = np.arange(0, 20, 2, dtype=np.int64)  # partition-0 rows
        lids = ids // 2  # their LOCAL rows, for direct server calls
        remote.push_gradients(ids, np.ones((10, 4), np.float32))
        key = "dv@p0"
        assert a.ps.replicas[key].role == "primary"
        assert b.ps.replicas[key].seq == a.ps.replicas[key].seq

        # a peer trainer failed partition 0 over: b is primary at epoch
        # 1 and applies the cluster's REAL next round
        cb = ps_server._Conn(b.ep)
        cb.call("promote", name="dv", partition=0, epoch=1, backups=[])
        cb.call("push_gradients", name="dv", ids=lids,
                grads=np.full((10, 4), 2.0, np.float32), partition=0,
                trainer_id=1, step=101)

        # a second trainer, its routing behind, writes to the OLD
        # primary: the apply lands locally, the forward to b is epoch-
        # rejected, and the deposed server latches stale — now holding
        # the SAME seq as the new primary but different row content
        ca = ps_server._Conn(a.ep, deadline=5.0)
        with pytest.raises(ps_server.StalePrimaryError):
            ca.call("push_gradients", name="dv", ids=lids,
                    grads=np.full((10, 4), -3.0, np.float32),
                    partition=0, trainer_id=2, step=101)
        rs_a = a.ps.replicas[key]
        assert rs_a.stale
        assert rs_a.seq == b.ps.replicas[key].seq
        assert not np.array_equal(a.ps.tables[key].to_dense(),
                                  b.ps.tables[key].to_dense())

        # anti-entropy from the stale replica MUST be a full transfer
        # (a seq-tail read as 'covered' would repair nothing)
        out = ca.call("resync", name="dv", partition=0, primary=b.ep,
                      self_endpoint=a.ep)
        assert out["mode"] == "full"
        np.testing.assert_array_equal(a.ps.tables[key].to_dense(),
                                      b.ps.tables[key].to_dense())
        assert not rs_a.stale and rs_a.role == "backup"

        # the repaired backup is re-enrolled in the forward set and
        # tracks the primary bit for bit again
        cb.call("push_gradients", name="dv", ids=lids,
                grads=np.ones((10, 4), np.float32), partition=0,
                trainer_id=1, step=102)
        np.testing.assert_array_equal(a.ps.tables[key].to_dense(),
                                      b.ps.tables[key].to_dense())
        cb.close()
        ca.close()
        remote.close()
    finally:
        for s in (a, b):
            try:
                s.kill()
            except Exception:
                pass


# ---------------------------------------------------------------------------
# hedged reads
# ---------------------------------------------------------------------------


def test_hedged_pull_first_response_wins(fast_failover):
    """A slow primary loses the race: after the latency histogram is
    warm, a backup-directed hedge fires at the observed quantile, its
    response wins, and the issued/won counters account for it — while
    the returned rows stay correct."""
    a, b = _Srv(), _Srv()
    try:
        kw = dict(num_shards=2, optimizer="sgd", learning_rate=0.5, seed=4)
        remote = ps_server.RemoteTable("h2", (100, 4), [a.ep, b.ep],
                                       replication=2, **kw)
        rng = np.random.RandomState(0)
        want = {}
        for i in range(ps_server.HEDGE_MIN_SAMPLES + 4):
            ids = rng.randint(0, 100, (8,)).astype(np.int64)
            want[i] = (ids, remote.gather(ids))
        # primary of partition 0 turns slow (500ms per gather)
        real = a.ps.gather

        def slow_gather(name, ids, partition=None):
            time.sleep(0.5)
            return real(name, ids, partition)

        a.ps.gather = slow_gather
        issued0 = _REG.counter("ps_client_hedges_issued_total",
                               verb="gather").value
        won0 = _REG.counter("ps_client_hedges_won_total",
                            verb="gather").value
        t0 = time.time()
        for i in range(4):
            ids, exp = want[i]
            np.testing.assert_array_equal(remote.gather(ids), exp)
        dt = time.time() - t0
        issued = _REG.counter("ps_client_hedges_issued_total",
                              verb="gather").value - issued0
        won = _REG.counter("ps_client_hedges_won_total",
                           verb="gather").value - won0
        assert issued > 0 and won > 0, (issued, won)
        # the slow path would cost >= 4 * 0.5s; hedging restores the tail
        assert dt < 4 * 0.5, dt
        remote.close()
    finally:
        for s in (a, b):
            s.kill()


# ---------------------------------------------------------------------------
# incremental snapshots
# ---------------------------------------------------------------------------


def _mk_spec(name, rows=20_000, dim=32):
    return {"name": name, "shape": (rows, dim), "num_shards": 4,
            "optimizer": "sgd", "learning_rate": 0.1, "seed": 1}


def test_incremental_snapshot_bytes_scale_with_touched_rows(tmp_path):
    """Acceptance: a cadence tick writes O(touched rows), not O(table).
    20k x 32 table: the base is ~2.5 MB; touching 50 rows must cost
    ~50 rows of delta, and an idle tick writes NOTHING."""
    srv = ps_server.PSServer(snapshot_dir=str(tmp_path),
                             snapshot_mode="incremental")
    srv.create_table(_mk_spec("big"))
    t = srv.tables["big"]
    assert srv.snapshot() == 1  # base
    base = [f for f in os.listdir(tmp_path) if ".base." in f][0]
    base_size = os.path.getsize(tmp_path / base)
    t.push_gradients(np.arange(50, dtype=np.int64),
                     np.ones((50, 32), np.float32))
    assert srv.snapshot() == 1  # one delta
    deltas = [f for f in os.listdir(tmp_path) if ".delta." in f]
    delta_size = sum(os.path.getsize(tmp_path / f) for f in deltas)
    assert delta_size * 50 < base_size, (delta_size, base_size)
    assert srv.snapshot() == 0  # idle tick: no bytes at all
    m = json.load(open(tmp_path / "manifest.json"))
    assert m["mode"] == "incremental"
    assert m["chains"]["big"]["deltas"][0]["rows"] == 50


def test_incremental_restore_equals_full_restore(tmp_path):
    """Acceptance: restore(base + delta chain) == restore(full). Drive
    the same table through both snapshotters and compare the restored
    dense states bit for bit (values AND adagrad accumulators ride)."""
    inc_dir, full_dir = tmp_path / "inc", tmp_path / "full"
    srv = ps_server.PSServer(snapshot_dir=str(inc_dir),
                             snapshot_mode="incremental")
    spec = _mk_spec("tbl", rows=500, dim=8)
    spec["optimizer"] = "adagrad"
    srv.create_table(dict(spec))
    t = srv.tables["tbl"]
    rng = np.random.RandomState(0)
    srv.snapshot()  # base
    for _ in range(3):  # three delta ticks of scattered updates
        ids = rng.randint(0, 500, (40,)).astype(np.int64)
        t.push_gradients(ids, rng.randn(40, 8).astype(np.float32))
        srv.snapshot()
    # same live table through a FULL snapshot
    srv_f = ps_server.PSServer(snapshot_dir=str(full_dir),
                               snapshot_mode="full")
    srv_f.tables["tbl"] = t
    srv_f.gens["tbl"] = 0
    srv_f.snapshot()

    def restore(preload):
        s = ps_server.PSServer(preload_dir=str(preload))
        s.create_table(dict(spec))
        return s.tables["tbl"]

    ti, tf = restore(inc_dir), restore(full_dir)
    np.testing.assert_array_equal(ti.to_dense(), tf.to_dense())
    np.testing.assert_array_equal(ti.to_dense(), t.to_dense())
    for s in range(t.num_shards):  # optimizer state restored identically
        np.testing.assert_array_equal(ti._accum[s], tf._accum[s])


def test_incremental_chain_compacts_and_cleans_up(tmp_path, monkeypatch):
    """Every N deltas the chain folds into a fresh base and superseded
    files are removed after the manifest commit — the directory never
    grows without bound."""
    monkeypatch.setattr(ps_server, "SNAPSHOT_COMPACT_EVERY", 3)
    srv = ps_server.PSServer(snapshot_dir=str(tmp_path),
                             snapshot_mode="incremental")
    srv.create_table(_mk_spec("c", rows=100, dim=4))
    t = srv.tables["c"]
    for _ in range(8):
        t.push_gradients(np.arange(5, dtype=np.int64),
                         np.ones((5, 4), np.float32))
        srv.snapshot()
    m = json.load(open(tmp_path / "manifest.json"))
    chain = m["chains"]["c"]
    assert len(chain["deltas"]) <= 3
    assert chain["base"].startswith("c.base.")
    referenced = {chain["base"]} | {d["file"] for d in chain["deltas"]}
    on_disk = {f for f in os.listdir(tmp_path) if f.endswith(".pkl")}
    assert on_disk == referenced, (on_disk, referenced)


def test_corrupt_delta_stops_chain_at_last_intact_file(tmp_path):
    """A corrupted delta (checksum mismatch) must not poison the
    restore: everything up to the last intact delta loads, the rest is
    skipped loudly."""
    srv = ps_server.PSServer(snapshot_dir=str(tmp_path),
                             snapshot_mode="incremental")
    srv.create_table(_mk_spec("k", rows=100, dim=4))
    t = srv.tables["k"]
    srv.snapshot()  # base
    t.push_gradients(np.arange(5, dtype=np.int64),
                     np.ones((5, 4), np.float32))
    srv.snapshot()  # delta 0 (intact)
    after_first = t.to_dense().copy()
    t.push_gradients(np.arange(5, 10, dtype=np.int64),
                     np.ones((5, 4), np.float32))
    srv.snapshot()  # delta 1 (to be corrupted)
    m = json.load(open(tmp_path / "manifest.json"))
    victim = m["chains"]["k"]["deltas"][1]["file"]
    with open(tmp_path / victim, "r+b") as f:
        f.seek(10)
        f.write(b"\xde\xad\xbe\xef")
    s2 = ps_server.PSServer(preload_dir=str(tmp_path))
    s2.create_table(_mk_spec("k", rows=100, dim=4))
    np.testing.assert_array_equal(s2.tables["k"].to_dense(), after_first)


# ---------------------------------------------------------------------------
# process layer (launcher end to end) — slow: replication chaos drills
# ---------------------------------------------------------------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _env(tmpdir, extra=None):
    env = dict(os.environ)
    for k in ("PADDLE_PSERVERS_IP_PORT_LIST", "PADDLE_TRAINERS_NUM",
              "PADDLE_PS_FAULT_SPEC", "FLAGS_ps_fault_injection",
              "PADDLE_PS_FAULT_TAGS", "PADDLE_PS_REPLICATION"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PADDLE_DIST_TRACE_DIR"] = str(tmpdir)
    env["PYTHONPATH"] = REPO
    env.update(extra or {})
    return env


def _launch_replicated(tmp_path, sub, extra_env=None, extra_args=(),
                       timeout=480):
    dist_dir = tmp_path / sub
    dist_dir.mkdir(exist_ok=True)
    log_dir = tmp_path / f"logs_{sub}"
    r = subprocess.run(
        [sys.executable, "-u", "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--started_port", str(_free_port()),
         "--server_num", "2", "--ps_replication", "2",
         "--log_dir", str(log_dir), *extra_args, WORKER],
        env=_env(dist_dir, extra_env), capture_output=True, text=True,
        timeout=timeout, cwd=REPO)
    logs = ""
    if log_dir.exists():
        for pth in sorted(log_dir.iterdir()):
            if pth.is_file():
                logs += f"\n--- {pth.name} ---\n" + pth.read_text()[-3000:]
    return r, dist_dir, logs


@pytest.mark.slow
def test_chaos_kill_primary_replicated_loss_parity(tmp_path):
    """Acceptance: R=2, kill ONE pserver mid-run (tag-scoped kill rule).
    Trainers fail over to the backups and finish; the loss trace is
    BIT-identical to the no-fault run of the same topology — replication
    makes a primary death invisible to the math, with no respawn-wait."""
    r_ref, ref_dir, logs = _launch_replicated(tmp_path, "ref")
    assert r_ref.returncode == 0, (
        f"no-fault run failed:\n{r_ref.stdout}\n{r_ref.stderr}\n{logs}")
    ref0 = json.load(open(ref_dir / "trace.0.json"))
    ref1 = json.load(open(ref_dir / "trace.1.json"))

    r, dist_dir, logs = _launch_replicated(
        tmp_path, "kill",
        extra_env={
            "FLAGS_ps_fault_injection": "1",
            "PADDLE_PS_FAULT_SPEC": "kill:*:30",
            "PADDLE_PS_FAULT_TAGS": "ps0",  # only ps0 dies
            "PADDLE_PS_CALL_DEADLINE_SECS": "2",
        },
        extra_args=("--elastic_retries", "1"))
    assert r.returncode == 0, (
        f"kill run failed rc={r.returncode}:\n{r.stdout}\n{r.stderr}\n{logs}")
    assert "promoting" in logs, f"no client failover observed:\n{logs}"
    t0 = json.load(open(dist_dir / "trace.0.json"))
    t1 = json.load(open(dist_dir / "trace.1.json"))
    assert t0["failovers"] + t1["failovers"] > 0, (t0, t1)
    # bit-identical: exact equality, not allclose
    assert t0["losses"] == ref0["losses"]
    assert t1["losses"] == ref1["losses"]
    assert t0["table_sum"] == ref0["table_sum"]
    assert t0["table_touched"] == ref0["table_touched"]


@pytest.mark.slow
def test_chaos_hedging_restores_tail_latency(tmp_path):
    """Acceptance: a server-side tail (every 4th gather on ps0 sleeps
    400ms) is absorbed by backup hedges — hedges are issued and WON, and
    the client's gather p95 stays well under the injected tail.

    The hedge quantile is set to p50 here deliberately: with a 25%
    injected tail, a p95-derived delay chases the tail itself (the
    histogram's p95 IS the injected latency) and hedges fire too late —
    exactly the situation the PADDLE_PS_HEDGE_QUANTILE knob exists for."""
    r, dist_dir, logs = _launch_replicated(
        tmp_path, "hedge",
        extra_env={
            "FLAGS_ps_fault_injection": "1",
            "PADDLE_PS_FAULT_SPEC": "slow:gather:4:400",
            "PADDLE_PS_FAULT_TAGS": "ps0",  # only the one replica is slow
            "PS_TEST_STEPS": "40",
            "PADDLE_PS_HEDGE_MIN_SAMPLES": "8",
            "PADDLE_PS_HEDGE_QUANTILE": "0.5",
        })
    assert r.returncode == 0, (
        f"hedge run failed rc={r.returncode}:\n{r.stdout}\n{r.stderr}\n{logs}")
    t0 = json.load(open(dist_dir / "trace.0.json"))
    t1 = json.load(open(dist_dir / "trace.1.json"))
    won = t0["hedges_won"] + t1["hedges_won"]
    assert won > 0, (t0, t1)
    # p95 restored: without hedging every 4th gather pins p95 at the
    # injected 400ms+; with hedges winning it stays below the tail
    assert min(t0["gather_p95_ms"], t1["gather_p95_ms"]) < 400, (t0, t1)
