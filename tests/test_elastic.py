"""Elastic world-size training + lease-based job control plane
(distributed/coordinator.py, ISSUE 8).

Fast layer (tier-1):
  - coordinator lease table: register/renew/expiry, per-rank budgets,
    eviction + membership epoch bumps, future-epoch (stale-coordinator)
    renewals rejected
  - lease-based pserver primary election: a primary killed with ZERO
    client traffic is replaced by a coordinator-granted promotion of
    the caught-up backup within 2 lease periods, observed via
    fleet.ps_stats() without issuing a data verb first
  - fault rules: lease_expire swallows renewals, netsplit drops RPCs
    for a window, flag-off is bit-identical
  - checkpoint manifests: world_size round-trip + refusal to resume a
    mismatched world when re-shard is disabled
  - launcher: per-rank budgets, eviction resize (3 -> 2) with re-ranked
    survivors and a restart line naming the dead tag + reason
  - debugz /flagz: GET state, POST mutation with audit, 403 off-list

Slow layer (tools/ci.sh elastic lane):
  - kill-one-of-four drill: a dp=4 job loses one trainer PERMANENTLY;
    the coordinator-backed launcher resizes to dp=3 from the last
    checkpoint and the post-resize loss trace is BIT-identical to a
    clean dp=3 run resumed from the same checkpoint step
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu import telemetry
from paddle_tpu.distributed import coordinator as coord_mod
from paddle_tpu.distributed import faults, ps, ps_server
from paddle_tpu.distributed.coordinator import (
    Coordinator, CoordinatorClient, LeaseWorker, serve_coordinator,
    stop_coordinator)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_elastic_worker.py")
_REG = telemetry.get_registry()


# ---------------------------------------------------------------------------
# coordinator unit layer (explicit clocks, no sleeps)
# ---------------------------------------------------------------------------


def test_register_renew_membership():
    c = Coordinator(lease_secs=1.0, retries_per_rank=1)
    t0 = 1000.0
    for i in range(3):
        out = c.register(f"trainer{i}", kind="trainer", now=t0)
        assert out == {"epoch": 0, "lease_secs": 1.0, "evicted": False}
    c.register("ps0", kind="pserver", endpoint="127.0.0.1:1", now=t0)
    m = c.membership(now=t0)
    assert m["epoch"] == 0 and m["world_size"] == 3
    assert m["members"]["ps0"]["kind"] == "pserver"
    # renewals refresh the lease: nobody expires while renewing
    for k in range(10):
        for i in range(3):
            c.renew(f"trainer{i}", payload={"step": k}, epoch=0,
                    now=t0 + k)
        assert c.sweep(now=t0 + k + 0.5) == []
    assert c.membership()["members"]["trainer1"]["payload"] == {"step": 9}


def test_lease_expiry_and_per_rank_budget_eviction():
    c = Coordinator(lease_secs=1.0, retries_per_rank=1, startup_grace=2.0)
    t0 = 1000.0
    for i in range(2):
        c.register(f"trainer{i}", now=t0)
        c.renew(f"trainer{i}", epoch=0, now=t0)
    # trainer1 stops renewing; expiry = renew + 2 lease periods
    c.renew("trainer0", epoch=0, now=t0 + 1.5)
    evs = c.sweep(now=t0 + 2.5)
    assert [e["event"] for e in evs] == ["lease_expired"]
    assert evs[0]["tag"] == "trainer1" and evs[0]["kind"] == "trainer"
    # one event per lapse, not one per sweep tick
    assert c.sweep(now=t0 + 3.0) == []
    # failure #1: within the per-rank budget -> restartable
    v = c.report_failure("trainer1", "lease expired")
    assert not v["evicted"] and v["epoch"] == 0 and v["retries_left"] == 0
    # the respawn re-registers and the lease lapse resets
    c.register("trainer1", now=t0 + 4.0)
    # failure #2: budget exhausted -> EVICTED, membership epoch bumps
    v = c.report_failure("trainer1", "nonzero exit (code 9)")
    assert v["evicted"] and v["epoch"] == 1
    assert c.membership()["world_size"] == 1
    # an evicted member renewing is told so and never resurrects
    out = c.renew("trainer1", epoch=0, now=t0 + 5.0)
    assert out["evicted"]
    out = c.register("trainer1", now=t0 + 5.0)
    assert out["evicted"]
    evs = [e["event"] for e in c.drain_events()]
    assert "member_failed" in evs and "member_evicted" in evs


def test_future_epoch_renewal_is_stale_coordinator_guard():
    """A renewal claiming a FUTURE membership epoch means a newer
    coordinator owns that member: the stale coordinator must not count
    it as liveness (no lease refresh) — the split-brain guard."""
    c = Coordinator(lease_secs=1.0, retries_per_rank=0, startup_grace=1.0)
    t0 = 1000.0
    c.register("trainer0", now=t0)
    c.renew("trainer0", epoch=0, now=t0)
    # same-epoch renewals keep the lease alive
    assert c.renew("trainer0", epoch=0, now=t0 + 1.0) == {
        "epoch": 0, "evicted": False}
    # future-epoch renewals are flagged and do NOT refresh
    out = c.renew("trainer0", epoch=5, now=t0 + 1.5)
    assert out.get("stale_coordinator")
    out = c.renew("trainer0", epoch=5, now=t0 + 2.5)
    assert out.get("stale_coordinator")
    # the lease therefore lapses at last good renewal + 2 periods
    evs = c.sweep(now=t0 + 3.5)
    assert [e["event"] for e in evs] == ["lease_expired"]
    assert any(e["event"] == "stale_coordinator"
               for e in c.drain_events())


def test_startup_grace_covers_slow_boot():
    """A registered member that has not renewed yet (imports, first XLA
    compile) is not expired until the startup grace runs out."""
    c = Coordinator(lease_secs=1.0, retries_per_rank=0,
                    startup_grace=10.0)
    t0 = 1000.0
    c.register("trainer0", now=t0)
    assert c.sweep(now=t0 + 5.0) == []  # inside grace, never renewed
    evs = c.sweep(now=t0 + 10.5)
    assert [e["event"] for e in evs] == ["lease_expired"]


def test_coordinator_over_rpc_transport():
    """The coordinator is hosted by the ps_server transport: register /
    renew / membership flow through real sockets, and a LeaseWorker
    keeps the lease alive from a background thread."""
    c = Coordinator(lease_secs=0.2, retries_per_rank=0, startup_grace=0.5)
    srv, ep = serve_coordinator(c)
    try:
        client = CoordinatorClient(ep, tag="trainer7", kind="trainer")
        assert client.register()["epoch"] == 0
        assert client.renew(payload={"step": 3})["evicted"] is False
        assert client.membership()["members"]["trainer7"][
            "payload"] == {"step": 3}
        client.close()
        worker = LeaseWorker(
            CoordinatorClient(ep, tag="trainer8"), interval=0.05,
            payload_fn=lambda: {"step": 1})
        worker.start()
        time.sleep(0.6)  # several renewal intervals
        # trainer7 went silent after one renewal (lapses); trainer8's
        # worker keeps its lease alive
        assert "trainer8" not in [e["tag"] for e in c.sweep()]
        worker.stop()
        time.sleep(0.6)  # > 2 lease periods with no renewals
        evs = c.sweep()
        assert [e["tag"] for e in evs] == ["trainer8"]
    finally:
        stop_coordinator(srv)


# ---------------------------------------------------------------------------
# lease-based pserver primary election (the acceptance drill)
# ---------------------------------------------------------------------------


class _Srv:
    """In-thread pserver on a real socket, hard-killable (the
    test_ps_replication harness)."""

    def __init__(self, port=0):
        self.ready = threading.Event()
        self.srv = None
        self.thread = threading.Thread(target=self._run, args=(port,),
                                       daemon=True)
        self.thread.start()
        assert self.ready.wait(10)

    def _run(self, port):
        self.srv = ps_server._TCPServer(("127.0.0.1", port),
                                        ps_server._Handler)
        self.srv.ps = ps_server.PSServer()
        self.ep = f"127.0.0.1:{self.srv.server_address[1]}"
        self.ready.set()
        self.srv.serve_forever(poll_interval=0.05)

    def kill(self):
        self.srv.shutdown()
        self.srv.close_all_connections()
        self.srv.server_close()
        self.thread.join(timeout=5)

    @property
    def ps(self):
        return self.srv.ps


@pytest.fixture
def replicated_pair(monkeypatch):
    monkeypatch.setattr(ps_server, "REPLICATED_DEADLINE_DEFAULT", 1.0)
    monkeypatch.setattr(ps_server, "REJOIN_SECS", 2.0)
    a, b = _Srv(), _Srv()
    ps._tables.pop("lease_tab", None)
    yield a, b
    ps.drop_table("lease_tab")
    for s in (a, b):
        try:
            s.kill()
        except Exception:  # noqa: BLE001 — already killed by the test
            pass


def test_coordinator_promotes_backup_without_client_traffic(
        replicated_pair):
    """ROADMAP's lease-based primary election: the primary dies while
    NO client is talking to the table. Its lease expires within 2
    periods, the coordinator elects the caught-up backup and promotes
    it DIRECTLY — asserted through fleet.ps_stats() (the idempotent
    observability verb) before any data verb is issued, with zero
    client-side failovers."""
    from paddle_tpu import fleet

    a, b = replicated_pair
    table = ps.create_table(
        "lease_tab", shape=(16, 4), num_shards=2, optimizer="sgd",
        learning_rate=0.5, seed=3, mode="async",
        endpoints=[a.ep, b.ep], replication=2)
    # drive a couple of writes so the backups hold a real seq prefix
    ids = np.arange(8, dtype=np.int64)
    table.push_gradients(ids, np.ones((8, 4), np.float32))
    table.push_gradients(ids, np.ones((8, 4), np.float32))

    lease = 0.25
    c = Coordinator(lease_secs=lease, retries_per_rank=0,
                    startup_grace=1.0)
    for tag, srv in (("ps0", a), ("ps1", b)):
        c.register(tag, kind="pserver", endpoint=srv.ep,
                   payload={"partitions": srv.ps.replica_summary()})
        c.renew(tag, payload={"partitions": srv.ps.replica_summary()})
    # partition 0's primary lives on server a, partition 1's on b
    assert a.ps.replica_summary()["lease_tab@p0"]["role"] == "primary"

    failovers_before = _REG.counter("ps_client_failovers_total").value
    a.kill()  # primary for p0 dies; the CLIENT stays silent
    t_kill = time.time()
    # the survivor keeps renewing; the dead primary's renewals stop
    promoted = []
    deadline = t_kill + 10 * lease
    while time.time() < deadline and not promoted:
        c.renew("ps1", payload={"partitions": b.ps.replica_summary()})
        promoted = [e for e in c.sweep()
                    if e.get("event") == "ps_promoted"]
        time.sleep(lease / 5)
    assert promoted, c.drain_events()
    elapsed = time.time() - t_kill
    assert elapsed <= 2 * lease + 1.0, elapsed  # within ~2 lease periods
    ev = promoted[0]
    assert ev["key"] == "lease_tab@p0" and ev["to"] == "ps1"

    # fleet.ps_stats() — an observability verb, not a data verb — shows
    # the coordinator-granted primary; the client issued no failover
    st = fleet.ps_stats("lease_tab")["lease_tab"]
    parts = {p["partition"]: p for p in st["replication"]["partitions"]}
    p0_roles = {r["endpoint"]: r.get("role")
                for r in parts[0]["replicas"]}
    assert p0_roles[b.ep] == "primary"
    assert any(r.get("epoch", 0) >= 1 for r in parts[0]["replicas"]
               if r["endpoint"] == b.ep)
    assert (_REG.counter("ps_client_failovers_total").value
            == failovers_before)

    # first client WRITE after the election: the routing adopts the
    # coordinator-granted primary via the bounce path (no extra epoch
    # bump over the grant)
    table.push_gradients(ids, np.ones((8, 4), np.float32))
    st0 = b.ps.replica_status("lease_tab@p0")
    assert st0["role"] == "primary" and st0["epoch"] == ev["epoch"]


# ---------------------------------------------------------------------------
# fault rules: lease_expire + netsplit
# ---------------------------------------------------------------------------


def test_lease_expire_rule_swallows_renewals(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_TAG", "trainer1")
    inj = faults.FaultInjector("lease_expire:trainer1:3")
    assert inj.on_lease_renew() is False
    assert inj.on_lease_renew() is False
    assert inj.on_lease_renew() is True  # 3rd renewal latches
    assert inj.on_lease_renew() is True  # latched forever


def test_lease_expire_rule_ignores_other_tags(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_TAG", "trainer0")
    inj = faults.FaultInjector("lease_expire:trainer1:1")
    for _ in range(5):
        assert inj.on_lease_renew() is False


def test_netsplit_rule_opens_and_heals_window(monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_TAG", "trainer0")
    inj = faults.FaultInjector("netsplit:trainer0:2:150")
    inj.before_send("gather")  # 1st RPC: no split yet
    with pytest.raises(faults.FaultError, match="netsplit"):
        inj.before_send("gather")  # 2nd fires the rule AND drops
    with pytest.raises(faults.FaultError, match="netsplit"):
        inj.before_send("push_gradients")  # every verb inside the window
    time.sleep(0.2)
    inj.before_send("gather")  # healed


def test_netsplit_requires_window_and_parse_roundtrip():
    with pytest.raises(ValueError, match="netsplit"):
        faults.parse_spec("netsplit:ps0:1")
    rules = faults.parse_spec("lease_expire:ps1:2;netsplit:*:1:500")
    assert [(r.action, r.method, r.nth, r.arg) for r in rules] == [
        ("lease_expire", "ps1", 2, 0.0), ("netsplit", "*", 1, 500.0)]


def test_fault_layer_off_is_inert(monkeypatch):
    """Spec set but flag off: injector() is None, so the lease client
    path takes zero fault branches — bit-identical to a build without
    the rules."""
    monkeypatch.setenv(faults.ENV_SPEC, "lease_expire:*:1;netsplit:*:1:99")
    monkeypatch.delenv("FLAGS_ps_fault_injection", raising=False)
    faults.reset()
    from paddle_tpu.fluid import flags

    monkeypatch.setitem(flags._values, "FLAGS_ps_fault_injection", False)
    assert faults.injector() is None
    faults.reset()


def test_netsplit_expires_lease_end_to_end(monkeypatch):
    """A netsplit on the member side drops its renewals at the
    transport layer, so the coordinator sees the lease lapse — the
    deterministic stand-in for a real partition."""
    from paddle_tpu.fluid import flags

    monkeypatch.setenv("PADDLE_TRAINER_TAG", "trainer3")
    # every RPC ATTEMPT counts: register is #1, the first renew #2, so
    # nth=3 opens the split on the second renew
    monkeypatch.setenv(faults.ENV_SPEC, "netsplit:trainer3:3:400")
    monkeypatch.setitem(flags._values, "FLAGS_ps_fault_injection", True)
    faults.reset()
    c = Coordinator(lease_secs=0.1, retries_per_rank=0, startup_grace=0.3)
    srv, ep = serve_coordinator(c)
    try:
        client = CoordinatorClient(ep, tag="trainer3", deadline=0.2)
        client.register()
        assert client.renew()["evicted"] is False  # before the split
        with pytest.raises(ConnectionError):
            client.renew()  # fires the rule and is dropped with it
        time.sleep(0.35)  # > 2 lease periods while split
        evs = c.sweep()
        assert [e["tag"] for e in evs] == ["trainer3"]
        client.close()
    finally:
        stop_coordinator(srv)
        faults.reset()


# ---------------------------------------------------------------------------
# checkpoint world-size gate
# ---------------------------------------------------------------------------


def test_checkpoint_world_size_roundtrip_and_refusal(tmp_path,
                                                     monkeypatch):
    from paddle_tpu.fluid import checkpoint as ckpt_mod
    from paddle_tpu.fluid import executor as executor_mod

    monkeypatch.delenv("PADDLE_ELASTIC_RESHARD", raising=False)
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    monkeypatch.setenv("PADDLE_MEMBERSHIP_EPOCH", "2")
    scope = executor_mod.Scope()
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), program=None,
                                     scope=scope)
    assert mgr.world_size == 4
    mgr.save(10, extra_state={"pos": 7})
    m = mgr.manifest(10)
    assert m["world_size"] == 4 and m["membership_epoch"] == 2

    # same world size: restores clean, reports what it restored
    st = mgr.restore()
    assert st["step"] == 10 and st["world_size"] == 4

    # resized world, re-shard DISABLED: refused loudly (never a silent
    # fallback — the older checkpoints have the same world size)
    mgr3 = ckpt_mod.CheckpointManager(str(tmp_path), program=None,
                                      scope=scope, world_size=3)
    with pytest.raises(ckpt_mod.WorldSizeMismatchError, match="4 train"):
        mgr3.restore()

    # re-shard enabled (arg or env): the resume proceeds and names the
    # world size the caller must re-split FROM
    st = mgr3.restore(allow_reshard=True)
    assert st["step"] == 10 and st["world_size"] == 4
    monkeypatch.setenv("PADDLE_ELASTIC_RESHARD", "1")
    assert mgr3.restore()["world_size"] == 4


def test_checkpoint_pre_elastic_manifests_skip_gate(tmp_path,
                                                    monkeypatch):
    """Checkpoints written without a world size (old manifests; no
    launcher env) restore under any world."""
    from paddle_tpu.fluid import checkpoint as ckpt_mod
    from paddle_tpu.fluid import executor as executor_mod

    monkeypatch.delenv("PADDLE_TRAINERS_NUM", raising=False)
    monkeypatch.delenv("PADDLE_ELASTIC_RESHARD", raising=False)
    scope = executor_mod.Scope()
    mgr = ckpt_mod.CheckpointManager(str(tmp_path), program=None,
                                     scope=scope)
    assert mgr.world_size is None
    mgr.save(5, extra_state={})
    assert "world_size" not in mgr.manifest(5)
    mgr3 = ckpt_mod.CheckpointManager(str(tmp_path), program=None,
                                      scope=scope, world_size=3)
    st = mgr3.restore()
    assert st["step"] == 5 and st["world_size"] is None


def test_ps_sync_trainers_updates_on_generation_bump():
    """The elastic-resize handshake: a create_table under a BUMPED
    generation may carry a new sync_trainers (the dp-mean denominator
    tracks the resize); without the bump a changed world is an error;
    everything else in the spec stays identity."""
    srv = ps_server.PSServer()
    spec = {"name": "t", "shape": (8, 2), "dtype": "float32",
            "num_shards": 2, "optimizer": "sgd", "learning_rate": 0.1,
            "initializer_std": None, "seed": 0, "sync_trainers": 4,
            "generation": 0}
    srv.create_table(dict(spec))
    assert srv.sync["t"].num == 4
    with pytest.raises(ValueError, match="generation"):
        srv.create_table(dict(spec, sync_trainers=3))  # no bump: refused
    before = srv.tables["t"].to_dense().copy()
    srv.create_table(dict(spec, sync_trainers=3, generation=1))
    assert srv.sync["t"].num == 3  # new dp-mean denominator
    assert srv.gens["t"] == 1
    np.testing.assert_array_equal(srv.tables["t"].to_dense(), before)
    with pytest.raises(ValueError, match="different spec"):
        srv.create_table(dict(spec, seed=9, generation=2))  # real clash


def _fit_model():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import layers
    from paddle_tpu.hapi import Input, Model

    def net(x):
        return layers.fc(x, 1)

    m = Model(net, Input("x", [4, 3]), Input("y", [4, 1]))
    m.prepare(fluid.optimizer.SGDOptimizer(learning_rate=0.1),
              lambda p, y: layers.mean(layers.square_error_cost(p, y)))
    return m


def test_fit_refuses_then_reshards_world_size_change(tmp_path,
                                                     monkeypatch):
    """Model.fit resume plumbing: a checkpoint from a dp=2 job resumed
    at dp=4 is REFUSED unless reshard is on; with reshard the per-rank
    position is scaled (old_step * old_w // new_w) so the global sample
    offset carries over."""
    import warnings as _warnings

    from paddle_tpu.fluid import checkpoint as ckpt_mod

    rng = np.random.RandomState(0)
    X = rng.randn(32, 3).astype(np.float32)
    Y = rng.randn(32, 1).astype(np.float32)
    ckpt_dir = str(tmp_path / "fit_ckpt")

    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    monkeypatch.delenv("PADDLE_ELASTIC_RESHARD", raising=False)
    m = _fit_model()
    m.fit((X, Y), batch_size=4, epochs=1, verbose=0, shuffle=False,
          checkpoint_dir=ckpt_dir, checkpoint_freq=4)
    mgr = m._checkpoint_manager(ckpt_dir)
    assert mgr.manifest(mgr.latest_step())["world_size"] == 2

    # resized world, no reshard: refusal, not a silent mis-shard
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    m2 = _fit_model()
    with pytest.raises(ckpt_mod.WorldSizeMismatchError):
        m2.fit((X, Y), batch_size=4, epochs=2, verbose=0, shuffle=False,
               checkpoint_dir=ckpt_dir, resume=True)

    # reshard on: resumes with the scaled position and finishes
    m3 = _fit_model()
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        hist = m3.fit((X, Y), batch_size=4, epochs=2, verbose=0,
                      shuffle=False, checkpoint_dir=ckpt_dir,
                      resume=True, reshard=True)
    assert any("elastic resume" in str(w.message) for w in caught)
    assert hist["loss"] and all(np.isfinite(hist["loss"]))


# ---------------------------------------------------------------------------
# launcher: per-rank budgets + eviction resize
# ---------------------------------------------------------------------------


def test_launch_per_rank_budget_evicts_and_resizes(tmp_path):
    """trainer1 is a permanently-lost host (per-rank budget 0): its
    first death EVICTS it, the membership epoch bumps, and the
    survivors restart re-ranked at world_size=2 — instead of the old
    whole-fleet budget burn. The restart line names the dead tag and
    the reason."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import os, sys, time
        out = sys.argv[1]
        tag = os.environ["PADDLE_TRAINER_TAG"]
        attempt = os.environ["PADDLE_ELASTIC_RESTART"]
        path = os.path.join(out, f"run.{attempt}.{tag}")
        with open(path + ".tmp", "w") as f:
            f.write("|".join([
                os.environ["PADDLE_TRAINER_ID"],
                os.environ["PADDLE_TRAINERS_NUM"],
                os.environ["PADDLE_MEMBERSHIP_EPOCH"],
                os.environ.get("PADDLE_ELASTIC_RESHARD", ""),
            ]))
        os.replace(path + ".tmp", path)  # whole, or not there
        if tag == "trainer1":
            # the launcher tears the attempt down at this exit: let the
            # other ranks of a loaded machine write theirs first
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not all(
                    os.path.exists(os.path.join(out, f"run.0.{t}"))
                    for t in ("trainer0", "trainer2")):
                time.sleep(0.05)
            sys.exit(5)
        """))
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "3", "--elastic_retries_per_rank", "0",
           "--elastic_retries", "3",
           str(script), str(tmp_path)]
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, (r.returncode, r.stderr)
    # attempt 0: full world of 3, epoch 0
    for tag in ("trainer0", "trainer1", "trainer2"):
        rank, world, epoch, reshard = (
            (tmp_path / f"run.0.{tag}").read_text().split("|"))
        assert world == "3" and epoch == "0"
    # attempt 1: trainer1 gone, survivors re-ranked 0..1, epoch bumped,
    # re-shard armed for the checkpoint world-size gate
    assert not (tmp_path / "run.1.trainer1").exists()
    rank0 = (tmp_path / "run.1.trainer0").read_text().split("|")
    rank2 = (tmp_path / "run.1.trainer2").read_text().split("|")
    assert rank0 == ["0", "2", "1", "1"]
    assert rank2 == ["1", "2", "1", "1"]
    # the restart line names who died and why
    assert "elastic restart 1/3" in r.stderr
    assert "trainer1" in r.stderr
    assert "nonzero exit (code 5)" in r.stderr
    assert "resizing to world_size=2" in r.stderr


def test_launch_within_budget_restarts_same_size(tmp_path):
    """A rank that fails INSIDE its per-rank budget restarts the group
    at the same world size — and the log names the culprit."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import os, sys
        tag = os.environ["PADDLE_TRAINER_TAG"]
        attempt = int(os.environ["PADDLE_ELASTIC_RESTART"])
        if tag == "trainer0" and attempt == 0:
            sys.exit(3)
        """))
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--elastic_retries", "2",
           str(script)]
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "elastic restart 1/2" in r.stderr
    assert "trainer0" in r.stderr
    assert "world_size=2" in r.stderr
    assert "resizing" not in r.stderr


def test_launch_min_world_size_aborts(tmp_path):
    """Eviction that would shrink below --min_world_size aborts instead
    of limping on."""
    script = tmp_path / "worker.py"
    script.write_text("import sys; sys.exit(6)\n")
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", "2", "--elastic_retries_per_rank", "0",
           "--elastic_retries", "4", "--min_world_size", "2",
           str(script)]
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 6, (r.returncode, r.stderr)
    assert "min_world_size" in r.stderr


# ---------------------------------------------------------------------------
# debugz /flagz
# ---------------------------------------------------------------------------


@pytest.fixture
def debugz_server(monkeypatch):
    from paddle_tpu.telemetry import debugz

    debugz.stop()
    srv = debugz.serve(port=0, host="127.0.0.1")
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    debugz.stop()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        resp = urllib.request.urlopen(req, timeout=5)
        return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_flagz_get_and_mutate_with_audit(debugz_server, tmp_path,
                                         monkeypatch):
    from paddle_tpu.fluid import flags
    from paddle_tpu.telemetry import sink

    audit_path = tmp_path / "metrics.jsonl"
    sink.enable(str(audit_path))
    try:
        state = json.loads(urllib.request.urlopen(
            debugz_server + "/flagz", timeout=5).read().decode())
        assert "FLAGS_check_numerics" in state["mutable"]
        assert state["values"]["FLAGS_check_numerics"] is False

        status, out = _post(debugz_server + "/flagz",
                            {"name": "FLAGS_check_numerics", "value": True})
        assert status == 200 and out["ok"]
        assert out["old"] is False and out["new"] is True
        assert flags.flag("FLAGS_check_numerics") is True

        # env-backed knob (straggler factor)
        status, out = _post(debugz_server + "/flagz",
                            {"name": "PADDLE_STRAGGLER_FACTOR",
                             "value": 2.5})
        assert status == 200 and os.environ[
            "PADDLE_STRAGGLER_FACTOR"] == "2.5"

        audits = [json.loads(l) for l in audit_path.read_text().splitlines()
                  if json.loads(l).get("kind") == "flagz_audit"]
        assert {a["flag"] for a in audits} == {
            "FLAGS_check_numerics", "PADDLE_STRAGGLER_FACTOR"}
        reg = telemetry.get_registry()
        assert reg.counter("debugz_flagz_mutations_total",
                           flag="FLAGS_check_numerics").value >= 1
    finally:
        sink.disable()
        flags.set_flags({"FLAGS_check_numerics": False})
        os.environ.pop("PADDLE_STRAGGLER_FACTOR", None)


def test_flagz_rejects_non_whitelisted_and_bad_requests(debugz_server):
    status, out = _post(debugz_server + "/flagz",
                        {"name": "FLAGS_conv_bn_fusion", "value": True})
    assert status == 403 and "not runtime-mutable" in out["error"]
    from paddle_tpu.fluid import flags

    assert flags.flag("FLAGS_conv_bn_fusion") is False  # untouched
    status, out = _post(debugz_server + "/flagz", {"value": 1})
    assert status == 400


# ---------------------------------------------------------------------------
# slow: the kill-one-of-four elastic resize drill
# ---------------------------------------------------------------------------


def _read_traces(trace_dir):
    """{(gs, rank): (loss, world)} keeping the LAST line per key — a
    replayed step (death between checkpoints) supersedes itself."""
    out = {}
    for name in sorted(os.listdir(trace_dir)):
        if not name.startswith("trace."):
            continue
        with open(os.path.join(trace_dir, name)) as f:
            for line in f:
                rec = json.loads(line)
                out[(rec["gs"], rec["rank"], rec["w"])] = rec["loss"]
    return out


def _launch_elastic(tmp_path, sub, nproc, extra_env, extra_args=()):
    logs = tmp_path / f"logs_{sub}"
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node", str(nproc), "--server_num", "1",
           "--log_dir", str(logs), *extra_args, WORKER]
    env = dict(os.environ, PYTHONPATH=REPO,
               PADDLE_PS_SYNC_TIMEOUT="30", **extra_env)
    env.pop("PADDLE_ELASTIC_RESHARD", None)
    env.update(extra_env)
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600), logs


@pytest.mark.slow
def test_kill_one_of_four_resizes_to_dp3_bit_exact(tmp_path):
    """ISSUE 8 acceptance: a dp=4 job loses trainer2 PERMANENTLY (it
    dies at the same step in every incarnation). Per-rank budget 1:
    death #1 restarts the group at dp=4 (budget spent), death #2
    EVICTS — the launcher resizes to dp=3 from the last checkpoint.
    The post-resize loss trace must be BIT-identical to a clean dp=3
    run resumed from the same checkpoint step."""
    ckpt = tmp_path / "ckpt"
    traces = tmp_path / "traces"
    ckpt.mkdir()
    traces.mkdir()
    r, logs = _launch_elastic(
        tmp_path, "drill", nproc=4,
        extra_env={
            "ELASTIC_TEST_DIR": str(ckpt),
            "ELASTIC_TEST_TRACE_DIR": str(traces),
            "ELASTIC_TEST_DIE_TAG": "trainer2",
            "ELASTIC_TEST_DIE_AT": "5",
            "ELASTIC_TEST_STEPS": "12",
            "ELASTIC_TEST_CKPT_FREQ": "2",
        },
        extra_args=("--elastic_retries", "4",
                    "--elastic_retries_per_rank", "1"))
    assert r.returncode == 0, (r.returncode, r.stderr[-4000:])
    assert "resizing to world_size=3" in r.stderr
    assert "trainer2" in r.stderr

    drill = _read_traces(traces)
    # dp=4 prefix ran, then the dp=3 continuation
    w4 = {(g, rk): v for (g, rk, w), v in drill.items() if w == 4}
    w3 = {(g, rk): v for (g, rk, w), v in drill.items() if w == 3}
    assert w4 and w3
    resize_start = min(g for g, _ in w3)
    assert set(rk for _, rk in w3) == {0, 1, 2}
    assert max(g for g, _ in w3) == 11  # ran to completion

    # clean parity run: dp=3 from scratch topology, resuming the SAME
    # checkpoint step the resized survivors resumed
    parity_traces = tmp_path / "parity_traces"
    parity_traces.mkdir()
    r2, _ = _launch_elastic(
        tmp_path, "parity", nproc=3,
        extra_env={
            "ELASTIC_TEST_DIR": str(ckpt),
            "ELASTIC_TEST_TRACE_DIR": str(parity_traces),
            "ELASTIC_TEST_STEPS": "12",
            "ELASTIC_TEST_CKPT_FREQ": "13",  # parity run writes nothing
            "ELASTIC_TEST_RESTORE_STEP": str(resize_start),
            "PADDLE_ELASTIC_RESHARD": "1",
        })
    assert r2.returncode == 0, (r2.returncode, r2.stderr[-4000:])
    parity = {(g, rk): v
              for (g, rk, w), v in _read_traces(parity_traces).items()}
    assert set(parity) == set(w3)
    for key in sorted(w3):
        assert w3[key] == parity[key], (
            key, w3[key], parity[key], "post-resize trace diverged")
