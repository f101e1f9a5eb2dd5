"""Multi-process launcher: `python -m paddle_tpu.distributed.launch
[--nproc_per_node N] [--ips a,b] train.py args...`

Parity surface: reference python/paddle/distributed/launch.py:193 +
utils.py (get_cluster:230, start_local_trainers:340,
watch_local_trainers:407 — abort the whole job when any child dies).

Env protocol per trainer (identical to the reference, consumed by
parallel/env.py):
  PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM /
  PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT

Fault tolerance: --elastic_retries supervises BOTH sides of the PS data
plane. Trainer groups are respawned after a failure (reference behavior
is fail-fast only), and pserver processes are watched the same way —
a dead pserver is restarted on its ORIGINAL port with --preload_dir
pointed at its periodic snapshot directory (ps_server.PSServer.snapshot:
atomic state_dict pickles), so trainers' retrying RPC clients reconnect
and the job loses at most one snapshot interval of table updates instead
of hanging (the reference launcher only watches trainers; a dead pserver
is a whole-job hang there).

Preemption: SIGTERM to the LAUNCHER is forwarded to every trainer and
the job gets --sigterm_grace seconds to finish its final checkpoints
(fluid/checkpoint.py training loops honor the signal at the next step
boundary) before being terminated — the TPU-pod eviction contract. A
SIGTERM'd TRAINER that checkpointed exits with
checkpoint.PREEMPTED_EXIT_CODE (75); like any nonzero exit it consumes
one --elastic_retries attempt, and the respawned trainer auto-resumes
from the latest valid checkpoint (Model.fit(resume=...)).

Cross-job PS state: when PADDLE_PS_SNAPSHOT_DIR names a STABLE directory
(not this launcher's tempdir), freshly spawned pservers preload from it
on FIRST start too — a new job adopts the previous job's tables (epoch +
generation recorded in the snapshot manifest.json) the way
fleet.init_server(model_dir) does manually.

TPU notes: one process per HOST is the supported topology (all local
chips belong to one PJRT client, and a chip belongs to one process at a
time); --nproc_per_node > 1 exists for CPU fleets and tests
(JAX_PLATFORMS=cpu) and is refused at start-up on a host with TPU chips
(ChipOwnershipError) — there the second child cannot initialize its
backend, and --elastic_retries would respawn it until the budget is
gone. Rendezvous is the JAX coordination service bootstrapped from the
first endpoint (no gen_nccl_id gRPC exchange).
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
from typing import List, Optional


class Trainer:
    def __init__(self, rank: int, endpoint: str, tag: Optional[str] = None):
        self.rank = rank
        self.endpoint = endpoint
        # stable membership identity: ranks are RE-NUMBERED when an
        # elastic resize shrinks the world, tags are not — per-rank
        # restart budgets and the coordinator's lease table key on tags
        self.tag = tag if tag is not None else f"trainer{rank}"
        self.proc: Optional[subprocess.Popen] = None
        self.log = None


class PServer:
    """One supervised pserver child: the respawn identity (idx, host,
    bound port) needed to restart it in place."""

    def __init__(self, idx: int, host: str, port: int,
                 proc: subprocess.Popen):
        self.idx = idx
        self.host = host
        self.port = port  # bound port — respawns MUST rebind it
        self.proc = proc

    @property
    def tag(self) -> str:
        return f"ps{self.idx}"


def get_cluster(ips: List[str], nproc_per_node: int, start_port: int):
    """[(rank, ip:port)] across all nodes (reference utils.get_cluster)."""
    out = []
    rank = 0
    for ip in ips:
        for i in range(nproc_per_node):
            out.append(Trainer(rank, f"{ip}:{start_port + i}"))
            rank += 1
    return out


def _parse_args(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="spawn and watch per-node trainer processes",
    )
    p.add_argument("--ips", "--cluster_node_ips", default="127.0.0.1",
                   help="comma-separated node ips (this script runs on each)")
    p.add_argument("--node_ip", default=None,
                   help="this node's ip (default: first of --ips)")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--log_dir", default=None)
    p.add_argument(
        "--elastic_retries", type=int, default=0,
        help="JOB-LEVEL cap on trainer-group restarts (trainers resume "
        "from their own checkpoints; PADDLE_ELASTIC_RESTART carries the "
        "attempt number), and restart budget for dead pservers "
        "(snapshot recovery). 0 = reference behavior: fail fast "
        "(utils.py:407) — unless --elastic_retries_per_rank arms the "
        "control plane on its own",
    )
    p.add_argument(
        "--elastic_retries_per_rank", type=int, default=None,
        help="PER-RANK restart budget (default: = --elastic_retries). "
        "A rank that fails MORE times than its budget is EVICTED from "
        "the membership instead of burning the job: the coordinator "
        "bumps the membership epoch and the surviving ranks restart "
        "from the last checkpoint at the REDUCED world size (elastic "
        "resize; needs PADDLE_ELASTIC_RESHARD-aware checkpoints). A "
        "permanently-lost host therefore costs its own budget, not the "
        "whole fleet's",
    )
    p.add_argument(
        "--min_world_size", type=int, default=1,
        help="abort instead of resizing below this many trainers",
    )
    p.add_argument(
        "--lease_secs", type=float, default=None,
        help="arm the lease-based job control plane "
        "(distributed/coordinator.py): the launcher hosts a membership "
        "coordinator, heartbeat stamps become lease renewals "
        "(PADDLE_COORDINATOR_ENDPOINT / PADDLE_LEASE_SECS exported to "
        "every child), a trainer lease expired for 2 periods is "
        "treated like a hang (kill + per-rank budget), and an expired "
        "PSERVER primary lease promotes a caught-up backup directly — "
        "no client in the loop. Default: PADDLE_LEASE_SECS if set, "
        "else off",
    )
    p.add_argument(
        "--coordinator_standby", action="store_true",
        help="control-plane HA (ISSUE 18): spawn a WARM-STANDBY "
        "coordinator beside the durable primary. The standby follows "
        "the primary's snapshot+WAL stream (repl_pull) and promotes "
        "itself when the primary's incarnation lease lapses; clients "
        "hold the ordered endpoint list (primary,standby) and fail "
        "over, with split-brain fenced by the incarnation number. "
        "Implies the process-hosted durable coordinator (as does "
        "setting PADDLE_COORD_SNAPSHOT_SECS); requires --lease_secs",
    )
    p.add_argument(
        "--straggler_eject_factor", type=float, default=0.0,
        help="EJECT (kill + per-rank budget, reason 'straggler "
        "ejection') a trainer whose step time exceeds this multiple of "
        "the median across ranks — the enforcement sibling of the "
        "diagnosis-only --straggler_factor. 0 = off",
    )
    p.add_argument(
        "--sigterm_grace", type=float, default=30.0,
        help="seconds the job gets to checkpoint after the launcher "
        "receives SIGTERM (forwarded to every trainer; training loops "
        "with a CheckpointManager write a final checkpoint and exit). "
        "After the grace window remaining trainers are terminated",
    )
    p.add_argument(
        "--heartbeat_timeout", type=float, default=0.0,
        help="treat a trainer as hung when its heartbeat file "
        "(distributed/heartbeat.py; stamped by init_parallel_env) goes "
        "stale for this many seconds — catches collective deadlocks that "
        "never exit. 0 = off",
    )
    p.add_argument(
        "--straggler_factor", type=float, default=0.0,
        help="log a structured `straggler` event when a trainer's step "
        "time exceeds this multiple of the median across ranks (step "
        "rates ride the heartbeat stamps; fluid/monitor.py publishes "
        "them automatically). Diagnosis only — the job keeps running. "
        "0 = off",
    )
    p.add_argument(
        "--trace_dir", default=None,
        help="collect per-process traces: trainers record host spans "
        "(PADDLE_TRACE_DIR contract, fluid/profiler.py) and dump "
        "trace.<rank>.json here at exit; causal step tracing "
        "(telemetry/tracing.py) is armed in every child — pservers and "
        "the coordinator dump span lanes + flightrec.<tag>.json flight "
        "records here too (tools/tracetop.py merges those into per-round "
        "critical paths). After the job the launcher merges everything "
        "into <trace_dir>/timeline.json (pid=rank — open in Perfetto / "
        "chrome://tracing)",
    )
    p.add_argument(
        "--fleetz_port", type=int, default=None,
        help="arm the FLEET goodput view (telemetry/goodput.py): "
        "every child classifies its wall-clock into a goodput/badput "
        "ledger (PADDLE_GOODPUT=1) and ships a bounded metrics "
        "snapshot + ledger summary on each lease renewal "
        "(PADDLE_FLEET_METRICS=1); the launcher serves debugz on THIS "
        "port with /fleetz (per-rank rollup, job goodput %%, worst "
        "incidents) and /fleetz/metrics (fleet-wide Prometheus "
        "exposition, per-rank labels — scrape ONE endpoint instead of "
        "N). Implies --lease_secs 5 when the lease plane is off. "
        "Default: PADDLE_FLEETZ_PORT if set, else off",
    )
    p.add_argument(
        "--debugz_port", type=int, default=None,
        help="arm every trainer's live introspection server "
        "(telemetry/debugz.py: /metrics /statusz /steps /proftop "
        "/healthz) with deterministic per-rank ports: rank r serves on "
        "debugz_port + r. Default: PADDLE_DEBUGZ_PORT if set (same "
        "offset rule), else off",
    )
    p.add_argument(
        "--server_num", type=int, default=0,
        help="spawn N local parameter-server processes "
        "(distributed/ps_server.py) on free ports and export "
        "PADDLE_PSERVERS_IP_PORT_LIST to the trainers (reference "
        "launch_ps.py). Servers outlive elastic restarts, so hosted "
        "tables survive a trainer-group respawn",
    )
    p.add_argument(
        "--servers", default="",
        help="explicit pserver endpoint list host:port,... — endpoints "
        "whose host matches this node are spawned here; the full list "
        "is exported to trainers (multi-node PS). Overrides --server_num",
    )
    p.add_argument(
        "--ps_snapshot_secs", type=float, default=None,
        help="pserver snapshot interval (atomic per-table state_dict "
        "pickles a supervised restart recovers from). Default: "
        "PADDLE_PS_SNAPSHOT_SECS if set, else 1.0 when --elastic_retries "
        "> 0 (supervision without snapshots would restart pservers "
        "EMPTY), else 0 (off)",
    )
    p.add_argument(
        "--ps_snapshot_mode", default=None,
        choices=[None, "full", "incremental"],
        help="pserver snapshot format: 'full' rewrites every table each "
        "tick (the default); 'incremental' writes a periodic base plus "
        "checksummed dirty-row delta files — O(touched rows) per tick, "
        "which makes sub-second --ps_snapshot_secs viable on multi-GB "
        "tables. Default: PADDLE_PS_SNAPSHOT_MODE if set, else full",
    )
    p.add_argument(
        "--ps_replication", type=int, default=None,
        help="replication factor R for hosted PS tables: each row "
        "partition gets a primary pserver plus R-1 prefix-consistent "
        "backups on distinct pservers (needs --server_num >= R). "
        "Trainers fail over to a backup when a primary dies — no "
        "respawn-wait — and hedge slow reads to backups; the supervisor "
        "respawn then rejoins via anti-entropy resync. Default: "
        "PADDLE_PS_REPLICATION if set, else 1 (today's unreplicated "
        "data plane)",
    )
    p.add_argument(
        "--serve", action="store_true",
        help="SERVING mode (paddle_tpu.inference.server): the "
        "positional argument is a saved inference-model dir, and each "
        "'trainer' slot runs one serving replica bound to its cluster "
        "endpoint (started_port + rank). The whole supervision stack "
        "applies unchanged — heartbeats, per-rank restart budgets, "
        "elastic respawn, --lease_secs lease renewals (kind="
        "'inference'), SIGTERM graceful drain — and extra args after "
        "the model dir pass through to the server (--max_batch, "
        "--queue_depth, ...)",
    )
    p.add_argument(
        "--serve_kv_cache", choices=["0", "1"], default=None,
        help="serving replicas: force the paged-KV generation path on "
        "(1) or off (0, the r19 padded recompute baseline) — exported "
        "as PADDLE_SERVE_KV_CACHE to every replica",
    )
    p.add_argument(
        "--serve_kv_pages", type=int, default=None,
        help="serving replicas: KV pool size in pages per replica "
        "(PADDLE_SERVE_KV_PAGES; default sizes from the HBM budget)",
    )
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _spawn_pserver(idx: int, host: str, port: int,
                   log_dir: Optional[str] = None,
                   snapshot_root: Optional[str] = None,
                   snapshot_secs: float = 0.0,
                   preload_snapshots: bool = False,
                   heartbeat_dir: Optional[str] = None,
                   log_mode: str = "w",
                   clear_fault_spec: bool = False) -> subprocess.Popen:
    """Fork one pserver child and wait for its bound-port banner; the
    caller learns the bound port via proc.ps_bound_port. Snapshots live
    in a PER-SERVER subdir of snapshot_root — each server hosts its own
    row PARTITION of a table under the same name, and a shared dir would
    let server 1's respawn silently preload server 0's rows whenever the
    partition geometries coincide. Respawns pass the original port and
    preload_snapshots=True (recovery)."""
    env = dict(os.environ)
    env["PADDLE_TRAINING_ROLE"] = "PSERVER"
    env["PADDLE_PS_RANK_TAG"] = f"ps{idx}"
    if clear_fault_spec:
        # a RESPAWNED pserver must not replay the deterministic fault
        # schedule from RPC-count zero — a `kill:*:N` drill means "kill
        # this server once", not "kill every incarnation", which would
        # burn the whole restart budget on one rule
        env.pop("PADDLE_PS_FAULT_SPEC", None)
    if heartbeat_dir:
        env["PADDLE_HEARTBEAT_DIR"] = heartbeat_dir
    snap = os.path.join(snapshot_root, f"ps{idx}") if snapshot_root else None
    cmd = [sys.executable, "-u", "-m",
           "paddle_tpu.distributed.ps_server",
           "--port", str(port), "--host", host]
    if preload_snapshots and snap:
        cmd += ["--preload_dir", snap]
    if snap and snapshot_secs > 0:
        cmd += ["--snapshot_dir", snap,
                "--snapshot_secs", str(snapshot_secs)]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    line = proc.stdout.readline()  # "[ps_server] listening on h:p"
    if "listening on" not in line:
        proc.kill()
        raise RuntimeError(f"pserver {idx} failed to start: {line!r}")
    proc.ps_bound_port = int(line.rsplit(":", 1)[1])
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        log = open(os.path.join(log_dir, f"serverlog.{idx}"), log_mode)
        log.write(line)

        def drain(p=proc, f=log):
            for ln in p.stdout:
                f.write(ln)
            f.close()
    else:
        def drain(p=proc):
            for _ in p.stdout:
                pass

    threading.Thread(target=drain, daemon=True).start()
    return proc


def start_pservers(server_num: int, servers: str, node_ip: str,
                   log_dir: Optional[str] = None,
                   snapshot_dir: Optional[str] = None,
                   snapshot_secs: float = 0.0,
                   heartbeat_dir: Optional[str] = None,
                   adopt_snapshots: bool = False):
    """Spawn this node's pserver processes (reference launch_ps.py
    start_procs). Returns (List[PServer], full_endpoint_list).
    --server_num spawns on launcher-chosen free ports (the child binds
    port 0 and reports the bound port on stdout, so there is no
    pick-then-bind race); --servers spawns the endpoints whose host is
    this node. adopt_snapshots (stable PADDLE_PS_SNAPSHOT_DIR): preload
    each server's snapshot partition on FIRST spawn, not just respawn —
    a new job adopts a previous job's tables."""
    pservers: List[PServer] = []

    def spawn(port: int, host: str, idx: int) -> int:
        proc = _spawn_pserver(idx, host, port, log_dir=log_dir,
                              snapshot_root=snapshot_dir,
                              snapshot_secs=snapshot_secs,
                              preload_snapshots=adopt_snapshots,
                              heartbeat_dir=heartbeat_dir)
        pservers.append(PServer(idx, host, proc.ps_bound_port, proc))
        return proc.ps_bound_port

    try:
        if servers:
            eps = [e.strip() for e in servers.split(",") if e.strip()]
            for i, ep in enumerate(eps):
                host, port = ep.rsplit(":", 1)
                if host in (node_ip, "127.0.0.1", "localhost"):
                    spawn(int(port), host, i)
            endpoints = eps
        else:
            endpoints = []
            for i in range(server_num):
                bound = spawn(0, "127.0.0.1", i)
                endpoints.append(f"127.0.0.1:{bound}")
    except BaseException:
        # partial startup must not orphan the servers already running
        terminate_pservers(pservers)
        raise
    return pservers, endpoints


def terminate_pservers(pservers: List[PServer]):
    for p in pservers:
        if p.proc.poll() is None:
            p.proc.terminate()
    for p in pservers:
        try:
            p.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.proc.kill()


class PServerSupervisor:
    """Poll pserver children and respawn the dead ones in place (same
    host:port — trainers hold the endpoint list; their RPC retry loop
    rides out the gap). Recovery state comes from the snapshot dir: the
    respawn preloads the latest atomic snapshot, and trainers that find
    their table missing re-create it (RemoteTable._call), restoring the
    Downpour bounded-staleness contract instead of losing the job.

    A shared restart budget (--elastic_retries) bounds flapping; with
    heartbeats enabled, a pserver process that freezes (stamps stale) is
    killed and handled through the same respawn path."""

    def __init__(self, pservers: List[PServer], retries: int,
                 log_dir: Optional[str], snapshot_dir: Optional[str],
                 snapshot_secs: float, heartbeat_dir: Optional[str] = None,
                 heartbeat_timeout: float = 0.0):
        self.pservers = pservers
        self.retries_left = int(retries)
        self.log_dir = log_dir
        self.snapshot_dir = snapshot_dir
        self.snapshot_secs = snapshot_secs
        self.heartbeat_dir = heartbeat_dir
        self.aborted = False  # budget gone: no point restarting trainers
        self.monitor = None
        if heartbeat_dir and heartbeat_timeout > 0:
            from .heartbeat import HeartBeatMonitor

            self.monitor = HeartBeatMonitor(
                heartbeat_dir, [p.tag for p in pservers], heartbeat_timeout)

    def check(self) -> Optional[int]:
        """None = all healthy (possibly after respawns); an int = abort
        the job with that exit code (restart budget exhausted)."""
        if self.monitor is not None:
            running = [p for p in self.pservers if p.proc.poll() is None]
            stale = set(self.monitor.stale_ranks(
                ranks=[p.tag for p in running]))
            for p in running:
                if p.tag in stale:
                    print(f"[launch] pserver {p.idx} ({p.host}:{p.port}) "
                          f"stopped heartbeating (frozen?); killing it "
                          f"for respawn", file=sys.stderr)
                    p.proc.kill()
                    p.proc.wait()
        for p in self.pservers:
            rc = p.proc.poll()
            if rc is None:
                continue
            if self.retries_left <= 0:
                print(f"[launch] pserver {p.idx} ({p.host}:{p.port}) "
                      f"exited with {rc} and no restarts remain; "
                      f"aborting the job", file=sys.stderr)
                self.aborted = True
                return rc if rc != 0 else 1
            self.retries_left -= 1
            print(f"[launch] pserver {p.idx} ({p.host}:{p.port}) exited "
                  f"with {rc}; restarting it on the same port "
                  f"(snapshot recovery, {self.retries_left} restarts "
                  f"left)", file=sys.stderr)
            try:
                p.proc = _spawn_pserver(
                    p.idx, p.host, p.port, log_dir=self.log_dir,
                    snapshot_root=self.snapshot_dir,
                    snapshot_secs=self.snapshot_secs,
                    preload_snapshots=True,
                    heartbeat_dir=self.heartbeat_dir, log_mode="a",
                    clear_fault_spec=True)
            except RuntimeError as e:
                print(f"[launch] pserver {p.idx} respawn failed: {e}; "
                      f"aborting the job", file=sys.stderr)
                self.aborted = True
                return 1
        return None


def _spawn_coordinator(host: str, port: int, state_dir: Optional[str],
                       lease_secs: float, per_rank: int,
                       snapshot_secs: float,
                       log_dir: Optional[str] = None,
                       standby_of: Optional[str] = None,
                       log_mode: str = "w",
                       clear_fault_spec: bool = False) -> subprocess.Popen:
    """Fork one process-hosted coordinator (durable control plane,
    ISSUE 18) and wait for its bound-port banner — the _spawn_pserver
    idiom: first spawns bind port 0 and report the bound port; respawns
    pass the original port so clients reconnect in place. The caller
    learns the port via proc.coord_bound_port."""
    env = dict(os.environ)
    role = "standby" if standby_of else "primary"
    # fault tag-scoping identity: PADDLE_PS_FAULT_TAGS=coord arms kill/
    # crash rules in the PRIMARY only (the standby answers to
    # coord-standby)
    env["PADDLE_PS_RANK_TAG"] = ("coord-standby" if standby_of
                                 else "coord")
    # the coordinator must not hold a lease on itself
    env.pop("PADDLE_COORDINATOR_ENDPOINT", None)
    env.pop("PADDLE_CKPT_BARRIER_ENDPOINT", None)
    if clear_fault_spec:
        # same rule as pserver respawns: a `crash:coord_verb:N` drill
        # means "crash the coordinator once", not every incarnation
        env.pop("PADDLE_PS_FAULT_SPEC", None)
    cmd = [sys.executable, "-u", "-m",
           "paddle_tpu.distributed.coordinator",
           "--host", host, "--port", str(port),
           "--lease_secs", str(lease_secs),
           "--retries_per_rank", str(per_rank),
           "--snapshot_secs", str(snapshot_secs)]
    if state_dir:
        cmd += ["--state_dir", state_dir]
    if standby_of:
        cmd += ["--standby_of", standby_of]
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    line = proc.stdout.readline()  # "[coordinator] listening on h:p"
    if "listening on" not in line:
        proc.kill()
        raise RuntimeError(
            f"{role} coordinator failed to start: {line!r}")
    proc.coord_bound_port = int(line.rsplit(":", 1)[1])
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        log = open(os.path.join(log_dir, f"coordlog.{role}"), log_mode)
        log.write(line)

        def drain(p=proc, f=log):
            for ln in p.stdout:
                f.write(ln)
            f.close()
    else:
        def drain(p=proc):
            for _ in p.stdout:
                pass

    threading.Thread(target=drain, daemon=True).start()
    return proc


class CoordinatorSupervisor:
    """Respawn a dead process-hosted coordinator in place — same port,
    same state dir, so the durable snapshot+WAL make the respawn resume
    exactly where the dead one stopped (bumped incarnation,
    reconciliation window armed). The budget is --elastic_retries, like
    the pserver supervisor — but unlike a pserver, a coordinator dead
    past its budget does NOT abort the job: the data plane keeps
    training in grace mode, and a warm standby (when armed) promotes
    itself."""

    def __init__(self, children: dict, retries: int, ledger=None):
        # children: role -> spawn record (proc + the _spawn_coordinator
        # kwargs needed to respawn it in place)
        self.children = children
        self.retries_left = int(retries)
        self.ledger = ledger

    def check(self) -> None:
        for role, ent in self.children.items():
            proc = ent.get("proc")
            if proc is None or proc.poll() is None:
                continue
            rc = proc.poll()
            detect_ts = time.time()
            if self.retries_left <= 0:
                if not ent.get("dead_reported"):
                    ent["dead_reported"] = True
                    print(f"[launch] {role} coordinator exited with "
                          f"{rc} and no restarts remain; clients stay "
                          f"in grace mode"
                          + (" (warm standby will promote itself)"
                             if len(self.children) > 1
                             and role == "primary" else ""),
                          file=sys.stderr)
                ent["proc"] = None
                continue
            self.retries_left -= 1
            print(f"[launch] {role} coordinator (port {ent['port']}) "
                  f"exited with {rc}; respawning on the same port from "
                  f"its durable state ({self.retries_left} restarts "
                  f"left)", file=sys.stderr)
            try:
                ent["proc"] = _spawn_coordinator(
                    ent["host"], ent["port"], ent["state_dir"],
                    ent["lease_secs"], ent["per_rank"],
                    ent["snapshot_secs"], log_dir=ent.get("log_dir"),
                    standby_of=ent.get("standby_of"), log_mode="a",
                    clear_fault_spec=True)
            except RuntimeError as e:
                print(f"[launch] {role} coordinator respawn failed: "
                      f"{e}; clients stay in grace mode",
                      file=sys.stderr)
                ent["proc"] = None
                continue
            if self.ledger is not None:
                try:
                    self.ledger.event(
                        event="coord_respawn", role=role, rc=rc,
                        detect_ts=round(detect_ts, 6),
                        respawn_ts=round(time.time(), 6))
                except Exception:  # noqa: BLE001 — accounting only
                    pass


class SigtermGrace:
    """Launcher-side preemption protocol: on SIGTERM, forward the signal
    to every live trainer (their training loops checkpoint and exit) and
    give the group `grace_secs` to drain before the watcher terminates
    whatever is left. install() chains any previous handler; trainers
    are registered per elastic attempt."""

    def __init__(self, grace_secs: float):
        self.grace_secs = float(grace_secs)
        self.requested = threading.Event()
        self.deadline: Optional[float] = None
        self.trainers: List[Trainer] = []

    def install(self) -> bool:
        try:
            prev = signal.getsignal(signal.SIGTERM)

            def _handler(sig, frame):
                self.requested.set()
                self.deadline = time.time() + self.grace_secs
                print("[launch] SIGTERM: forwarding to trainers for a "
                      f"final checkpoint ({self.grace_secs}s grace)",
                      file=sys.stderr)
                for t in self.trainers:
                    if t.proc is not None and t.proc.poll() is None:
                        try:
                            t.proc.send_signal(signal.SIGTERM)
                        except OSError:
                            pass
                if callable(prev) and prev not in (signal.SIG_IGN,
                                                   signal.SIG_DFL):
                    prev(sig, frame)

            signal.signal(signal.SIGTERM, _handler)
            return True
        except ValueError:  # not the main thread (tests calling launch())
            return False

    def expired(self) -> bool:
        return self.deadline is not None and time.time() > self.deadline


def start_local_trainers(cluster: List[Trainer], node_ip: str, script: str,
                         script_args: List[str], log_dir: Optional[str],
                         restart_count: int = 0,
                         heartbeat_dir: Optional[str] = None,
                         debugz_base_port: Optional[int] = None,
                         membership_epoch: int = 0,
                         module: Optional[str] = None,
                         only_tags=None):
    """Fork this node's trainers with the env protocol (reference
    utils.start_local_trainers:340). debugz_base_port arms each rank's
    introspection server on base + rank (deterministic: operators and
    scrape configs can address any rank's /metrics without discovery).
    PADDLE_TRAINER_TAG carries the stable membership identity and
    PADDLE_MEMBERSHIP_EPOCH the coordinator's membership epoch — both
    survive resizes where the rank numbering does not."""
    endpoints = ",".join(t.endpoint for t in cluster)
    local = [t for t in cluster if t.endpoint.split(":")[0] == node_ip]
    if only_tags is not None:
        # per-replica respawn (--serve): spawn ONLY the named members,
        # with the env protocol still derived from the full cluster
        local = [t for t in local if t.tag in only_tags]
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    for t in local:
        env = dict(os.environ)
        env.update(
            PADDLE_TRAINER_ID=str(t.rank),
            PADDLE_TRAINERS_NUM=str(len(cluster)),
            PADDLE_TRAINER_ENDPOINTS=endpoints,
            PADDLE_CURRENT_ENDPOINT=t.endpoint,
            PADDLE_ELASTIC_RESTART=str(restart_count),
            PADDLE_TRAINER_TAG=t.tag,
            PADDLE_MEMBERSHIP_EPOCH=str(membership_epoch),
        )
        if debugz_base_port is not None:
            env["PADDLE_DEBUGZ_PORT"] = str(debugz_base_port + t.rank)
        if heartbeat_dir:
            env["PADDLE_HEARTBEAT_DIR"] = heartbeat_dir
        # module mode (launch --serve): run `-m <module>` instead of a
        # script file — the serving replica binds its cluster endpoint's
        # port via PADDLE_CURRENT_ENDPOINT
        if module is not None:
            cmd = [sys.executable, "-u", "-m", module] + list(script_args)
        else:
            cmd = [sys.executable, "-u", script] + list(script_args)
        if log_dir:
            mode = "a" if restart_count else "w"
            t.log = open(os.path.join(log_dir, f"workerlog.{t.rank}"), mode)
            t.proc = subprocess.Popen(cmd, env=env, stdout=t.log,
                                      stderr=subprocess.STDOUT)
        else:
            t.proc = subprocess.Popen(cmd, env=env)
    return local


def terminate_local_trainers(trainers: List[Trainer]):
    for t in trainers:
        if t.proc and t.proc.poll() is None:
            t.proc.terminate()
    deadline = time.time() + 5
    for t in trainers:
        if not t.proc:
            continue
        while t.proc.poll() is None and time.time() < deadline:
            time.sleep(0.05)
        if t.proc.poll() is None:
            t.proc.kill()
    for t in trainers:
        if t.log:
            t.log.close()


class ServeRespawner:
    """Per-replica supervision for launch --serve: serving replicas are
    INDEPENDENT — one dying must never blip the rest of the fleet, so
    (unlike sync training, where the barrier demands a group restart) a
    dead replica is respawned IN PLACE on its original endpoint, budget
    `--elastic_retries` per replica. Past budget the death falls through
    to the group-abort path so the job still ends loudly."""

    def __init__(self, cluster: List[Trainer], node_ip: str, script: str,
                 script_args: List[str], log_dir: Optional[str],
                 retries: int, heartbeat_dir: Optional[str] = None,
                 debugz_base_port: Optional[int] = None,
                 membership_epoch: int = 0,
                 module: Optional[str] = None):
        self.cluster = cluster
        self.node_ip = node_ip
        self.script = script
        self.script_args = list(script_args)
        self.log_dir = log_dir
        self.retries = int(retries)
        self.heartbeat_dir = heartbeat_dir
        self.debugz_base_port = debugz_base_port
        self.membership_epoch = membership_epoch
        self.module = module
        self._counts: dict = {}

    def respawn(self, t: Trainer) -> bool:
        n = self._counts.get(t.tag, 0)
        if n >= self.retries:
            return False
        self._counts[t.tag] = n + 1
        print(f"[launch] serving replica {t.rank} ({t.tag}, "
              f"{t.endpoint}) died; respawning in place "
              f"({n + 1}/{self.retries}); the rest of the fleet keeps "
              f"serving", file=sys.stderr, flush=True)
        start_local_trainers(
            self.cluster, self.node_ip, self.script, self.script_args,
            self.log_dir, restart_count=n + 1,
            heartbeat_dir=self.heartbeat_dir,
            debugz_base_port=self.debugz_base_port,
            membership_epoch=self.membership_epoch, module=self.module,
            only_tags={t.tag})
        return True


def watch_local_trainers(trainers: List[Trainer], poll_interval=0.2,
                         monitor=None, ps_supervisor=None,
                         grace: Optional[SigtermGrace] = None,
                         straggler=None, failure: Optional[dict] = None,
                         coordinator=None, straggler_eject=False,
                         serve_respawner: Optional[ServeRespawner] = None,
                         fleet_ledger=None, incident_coord=None,
                         coord_supervisor=None,
                         ) -> int:
    """Block until all trainers exit. Any nonzero exit — or a stale
    heartbeat when `monitor` (heartbeat.HeartBeatMonitor) is given —
    aborts the whole local group (reference watch_local_trainers:407:
    fail fast; heartbeat parity: heart_beat_monitor.h:54). A
    `ps_supervisor` (PServerSupervisor) is polled on the same cadence:
    it respawns dead pservers in place, or returns an exit code to abort
    with when the restart budget is gone. Under a SIGTERM `grace` the
    watcher waits for the (already signaled) trainers to finish their
    final checkpoints, terminating stragglers when the grace window
    expires, and reports 128+SIGTERM. Returns the job's exit code.

    `failure` (out-param dict) receives {"trainer", "tag", "reason"}
    for the trainer whose death ended the watch — the attempts loop
    charges the right PER-RANK budget and names the culprit in the
    restart line. `coordinator` (coordinator.Coordinator) is swept on
    the poll cadence: an expired TRAINER lease is treated like a hang
    (kill + reason "lease expired"), and expired PSERVER primary
    leases trigger backup promotion inside the sweep. With
    `straggler_eject`, a straggler event kills the dragging rank
    (reason "straggler ejection") instead of only logging it."""

    def _fail(t: Optional[Trainer], reason: str) -> None:
        if failure is not None and t is not None:
            failure.update(trainer=t, tag=t.tag, rank=t.rank,
                           reason=reason)

    try:
        while True:
            if grace is not None and grace.requested.is_set():
                # preemption drain: children got SIGTERM from the grace
                # handler; each checkpoints and exits on its own
                while (any(t.proc.poll() is None for t in trainers)
                       and not grace.expired()):
                    time.sleep(poll_interval)
                terminate_local_trainers(trainers)
                return 128 + signal.SIGTERM
            alive = False
            for t in trainers:
                rc = t.proc.poll()
                if rc is None:
                    alive = True
                elif rc != 0:
                    if serve_respawner is not None \
                            and serve_respawner.respawn(t):
                        alive = True  # replaced in place; fleet serves on
                        continue
                    print(
                        f"[launch] trainer {t.rank} ({t.tag}, "
                        f"{t.endpoint}) exited with {rc}; aborting the "
                        f"job",
                        file=sys.stderr,
                    )
                    _fail(t, f"nonzero exit (code {rc})")
                    terminate_local_trainers(trainers)
                    return rc
            if not alive:
                return 0
            if monitor is not None:
                running = [t.rank for t in trainers if t.proc.poll() is None]
                stale = monitor.stale_ranks(ranks=running)
                if stale:
                    print(
                        f"[launch] trainer rank(s) {stale} stopped "
                        f"heartbeating for >{monitor.timeout}s (hang?); "
                        f"aborting the group",
                        file=sys.stderr,
                    )
                    culprit = next((t for t in trainers
                                    if t.rank in stale), None)
                    _fail(culprit, "heartbeat stale (hang)")
                    terminate_local_trainers(trainers)
                    return 124  # timeout-style exit code
            if coordinator is not None:
                # lease plane: sweep expiries (and pserver primary
                # elections) on the watch cadence, then react to
                # expired TRAINER leases exactly like stale heartbeats
                events = coordinator.sweep()
                running_tags = {t.tag: t for t in trainers
                                if t.proc.poll() is None}
                for ev in events:
                    if (ev.get("event") == "lease_expired"
                            and ev.get("kind") == "trainer"
                            and ev.get("tag") in running_tags):
                        t = running_tags[ev["tag"]]
                        print(f"[launch] trainer {t.rank} ({t.tag}) "
                              f"lease expired ({ev.get('overdue_s')}s "
                              f"overdue — renewals stopped); killing "
                              f"the group", file=sys.stderr)
                        _fail(t, "lease expired (no renewals)")
                        terminate_local_trainers(trainers)
                        return 124
            if straggler is not None:
                # one structured JSON line per episode
                # (heartbeat.StragglerMonitor); diagnosis by default,
                # ejection when the eject factor armed this watch
                from ..telemetry.straggler import format_event

                for ev in straggler.poll():
                    print(format_event(ev), file=sys.stderr, flush=True)
                    # goodput (ISSUE 15): a straggler episode is badput
                    # — one `stall` event in the launcher ledger (with
                    # the culprit's step trace_id, the same hop tracetop
                    # blames) and the coordinator's incident ring
                    culprit_tag = next(
                        (t.tag for t in trainers
                         if str(t.rank) == str(ev.get("rank"))), None)
                    stall_ev = {
                        "event": "stall", "rank": ev.get("rank"),
                        "tag": culprit_tag, "step": ev.get("step"),
                        "excess_ms": ev.get("excess_ms"),
                        "slowdown": ev.get("slowdown"),
                        "cause": ev.get("cause", "compute"),
                        "trace_id": ev.get("trace_id"),
                    }
                    if fleet_ledger is not None:
                        fleet_ledger.event(**stall_ev)
                    if incident_coord is not None:
                        try:
                            incident_coord.note_incident(stall_ev)
                        except Exception:  # noqa: BLE001 — accounting
                            pass
                    if straggler_eject:
                        culprit = next(
                            (t for t in trainers
                             if str(t.rank) == str(ev.get("rank"))
                             and t.proc.poll() is None), None)
                        if culprit is not None:
                            print(f"[launch] trainer {culprit.rank} "
                                  f"({culprit.tag}) ejected as a "
                                  f"straggler", file=sys.stderr)
                            _fail(culprit, "straggler ejection")
                            terminate_local_trainers(trainers)
                            return 124
            if ps_supervisor is not None:
                rc = ps_supervisor.check()
                if rc is not None:
                    terminate_local_trainers(trainers)
                    return rc
            if coord_supervisor is not None:
                # durable control plane (ISSUE 18): respawn a dead
                # coordinator in place; never aborts the job
                coord_supervisor.check()
            time.sleep(poll_interval)
    except KeyboardInterrupt:
        terminate_local_trainers(trainers)
        return 128 + signal.SIGINT


class ChipOwnershipError(RuntimeError):
    """--nproc_per_node > 1 on a host whose TPU chips every child would
    claim."""


def _local_tpu_chips() -> List[str]:
    """The device nodes libtpu opens, found without importing jax: the
    launcher stays off JAX so that it never holds a chip itself."""
    import glob

    return sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))


def _check_one_process_per_chip_host(nproc_per_node: int) -> None:
    if nproc_per_node <= 1:
        return
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        return  # a CPU fleet: the children never open a chip
    chips = _local_tpu_chips()
    if chips:
        raise ChipOwnershipError(
            f"--nproc_per_node {nproc_per_node} on a host with TPU chips "
            f"({', '.join(chips)}): each child process would claim every "
            f"local chip, and a chip belongs to one process at a time — "
            f"all but one fail with \"Unable to initialize backend "
            f"'tpu'\" and are respawned until the restart budget is "
            f"gone. Run ONE process per host (it drives all local chips; "
            f"serving replicas go one per host), or set JAX_PLATFORMS=cpu "
            f"for a CPU fleet.")


def launch(argv=None) -> int:
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    _check_one_process_per_chip_host(args.nproc_per_node)
    ips = [s.strip() for s in args.ips.split(",") if s.strip()]
    node_ip = args.node_ip or ips[0]
    cluster = get_cluster(ips, args.nproc_per_node, args.started_port)

    # lease plane (--lease_secs / PADDLE_LEASE_SECS): the launcher hosts
    # the membership coordinator and every child renews a lease on it
    lease_secs = args.lease_secs
    if lease_secs is None:
        try:
            lease_secs = float(os.environ.get("PADDLE_LEASE_SECS", 0) or 0)
        except ValueError:
            lease_secs = 0.0

    # fleet goodput view (--fleetz_port / PADDLE_FLEETZ_PORT): ledger in
    # every child, bounded snapshots on renewals, one launcher-side
    # scrape endpoint. Rides the lease plane — renewals ARE the push
    # channel — so arming it arms leases too
    fleetz_port = args.fleetz_port
    if fleetz_port is None:
        raw = os.environ.get("PADDLE_FLEETZ_PORT")
        if raw:
            try:
                fleetz_port = int(raw)
            except ValueError:
                fleetz_port = None
    if fleetz_port is not None:
        if lease_secs <= 0:
            lease_secs = 5.0
            print("[launch] --fleetz_port arms the lease plane "
                  "(renewals carry the fleet payloads); defaulting "
                  "--lease_secs 5", file=sys.stderr)
        # children inherit through the spawn env copies
        os.environ["PADDLE_GOODPUT"] = "1"
        os.environ["PADDLE_FLEET_METRICS"] = "1"

    heartbeat_dir = None
    own_heartbeat_dir = False
    # straggler detection and lease renewals ride the same heartbeat
    # channel (stamps carry step counts and double as renewals), so any
    # of these flags provisions the directory
    if (args.heartbeat_timeout > 0 or args.straggler_factor > 0
            or args.straggler_eject_factor > 0 or lease_secs > 0):
        heartbeat_dir = os.environ.get("PADDLE_HEARTBEAT_DIR")
        if not heartbeat_dir:
            import tempfile

            heartbeat_dir = tempfile.mkdtemp(prefix="paddle_tpu_hb_")
            own_heartbeat_dir = True

    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        # trainers inherit it via start_local_trainers' env copy and
        # auto-dump per-rank traces (profiler.maybe_start_trace_collection)
        os.environ["PADDLE_TRACE_DIR"] = args.trace_dir
        # --trace_dir is an explicit observability opt-in: arm causal
        # span tracing (telemetry/tracing.py) in every child AND this
        # launcher (the coordinator's lane) unless the operator pinned
        # it off; the flight recorder then dumps per-process spans here
        os.environ.setdefault("PADDLE_TRACING", "1")

    # snapshot interval: explicit flag > env > supervision-implied default
    snapshot_secs = args.ps_snapshot_secs
    if snapshot_secs is None:
        env_secs = os.environ.get("PADDLE_PS_SNAPSHOT_SECS")
        if env_secs:
            snapshot_secs = float(env_secs)
        else:
            snapshot_secs = 1.0 if args.elastic_retries > 0 else 0.0

    grace = SigtermGrace(args.sigterm_grace)
    grace.install()

    # the job control plane: the coordinator owns membership, epochs and
    # per-rank budgets whenever elastic supervision is on; it is SERVED
    # over TCP (lease renewals) only when --lease_secs arms leases.
    # DURABLE mode (ISSUE 18 — PADDLE_COORD_SNAPSHOT_SECS set, or
    # --coordinator_standby): the coordinator moves OUT of the launcher
    # into a supervised child process with snapshot+WAL state, and the
    # launcher talks to it through CoordinatorProxy; neither armed =
    # the in-process coordinator, byte-identical on the wire
    from .coordinator import (Coordinator, CoordinatorProxy,
                              serve_coordinator, stop_coordinator)

    per_rank = (args.elastic_retries_per_rank
                if args.elastic_retries_per_rank is not None
                else args.elastic_retries)
    durable_snap_secs = None
    raw_snap = os.environ.get("PADDLE_COORD_SNAPSHOT_SECS")
    if raw_snap:
        try:
            durable_snap_secs = float(raw_snap)
        except ValueError:
            durable_snap_secs = None
    durable_coord = lease_secs > 0 and (durable_snap_secs is not None
                                        or args.coordinator_standby)
    if args.coordinator_standby and lease_secs <= 0:
        print("[launch] --coordinator_standby needs the lease plane; "
              "arm it with --lease_secs", file=sys.stderr)
        return 2
    coord_server = None
    coord_children = None
    own_coord_state = False
    coord_state_root = None
    coord_ep = None
    if durable_coord:
        snap_secs = (durable_snap_secs
                     if durable_snap_secs is not None else 1.0)
        if args.log_dir:
            coord_state_root = os.path.join(args.log_dir, "coord_state")
        else:
            import tempfile

            coord_state_root = tempfile.mkdtemp(
                prefix="paddle_tpu_coord_")
            own_coord_state = True
        os.makedirs(coord_state_root, exist_ok=True)
        primary_state = os.path.join(coord_state_root, "primary")
        primary = _spawn_coordinator(
            "127.0.0.1", 0, primary_state, lease_secs, per_rank,
            snap_secs, log_dir=args.log_dir)
        primary_ep = f"127.0.0.1:{primary.coord_bound_port}"
        coord_children = {"primary": {
            "proc": primary, "host": "127.0.0.1",
            "port": primary.coord_bound_port,
            "state_dir": primary_state, "lease_secs": lease_secs,
            "per_rank": per_rank, "snapshot_secs": snap_secs,
            "log_dir": args.log_dir, "standby_of": None}}
        endpoints = [primary_ep]
        if args.coordinator_standby:
            standby_state = os.path.join(coord_state_root, "standby")
            standby = _spawn_coordinator(
                "127.0.0.1", 0, standby_state, lease_secs, per_rank,
                snap_secs, log_dir=args.log_dir, standby_of=primary_ep)
            coord_children["standby"] = {
                "proc": standby, "host": "127.0.0.1",
                "port": standby.coord_bound_port,
                "state_dir": standby_state, "lease_secs": lease_secs,
                "per_rank": per_rank, "snapshot_secs": snap_secs,
                "log_dir": args.log_dir, "standby_of": primary_ep}
            endpoints.append(f"127.0.0.1:{standby.coord_bound_port}")
        coord_ep = ",".join(endpoints)
        # children inherit the ORDERED list through the spawn env copies
        os.environ["PADDLE_COORDINATOR_ENDPOINT"] = coord_ep
        os.environ["PADDLE_LEASE_SECS"] = str(lease_secs)
        coord = CoordinatorProxy(coord_ep, lease_secs, per_rank)
        print(f"[launch] durable job coordinator on {coord_ep} (lease "
              f"{lease_secs}s, per-rank budget {per_rank}, snapshots "
              f"every {snap_secs}s"
              + (", warm standby" if args.coordinator_standby else "")
              + ")", file=sys.stderr)
    else:
        coord = Coordinator(lease_secs=lease_secs or 5.0,
                            retries_per_rank=per_rank)
        if lease_secs > 0:
            coord_server, coord_ep = serve_coordinator(coord)
            # children inherit both through the spawn env copies
            os.environ["PADDLE_COORDINATOR_ENDPOINT"] = coord_ep
            os.environ["PADDLE_LEASE_SECS"] = str(lease_secs)
            print(f"[launch] job coordinator on {coord_ep} (lease "
                  f"{lease_secs}s, per-rank budget {per_rank})",
                  file=sys.stderr)

    # goodput ledgers (PADDLE_GOODPUT, armed by --fleetz_port or set by
    # the operator): children persist per-incarnation interval files and
    # the launcher keeps a lifecycle ledger (restart detect/respawn
    # timestamps, straggler stalls) goodtop stitches them with
    fleet_ledger = None
    fleet_exporter = None
    goodput_armed = os.environ.get("PADDLE_GOODPUT", "") not in (
        "", "0", "false")
    if goodput_armed:
        goodput_dir = (os.environ.get("PADDLE_GOODPUT_DIR")
                       or os.environ.get("PADDLE_TRACE_DIR"))
        if not goodput_dir and args.log_dir:
            goodput_dir = os.path.join(args.log_dir, "goodput")
        if goodput_dir:
            os.makedirs(goodput_dir, exist_ok=True)
            # children inherit it through the spawn env copies
            os.environ["PADDLE_GOODPUT_DIR"] = goodput_dir
            from ..telemetry.goodput import LauncherLedger

            fleet_ledger = LauncherLedger(goodput_dir)
            fleet_ledger.event(event="job_start", world=len(cluster),
                               tags=[t.tag for t in cluster],
                               lease_secs=lease_secs)
    if durable_coord:
        # the proxy records coord_outage windows into the same ledger
        # goodtop stitches (distinct from rank-death restarts)
        coord.ledger = fleet_ledger
    if fleetz_port is not None:
        from ..telemetry import debugz as _debugz

        try:
            fleet_srv = _debugz.serve(fleetz_port)
            print(f"[launch] fleet view on port "
                  f"{fleet_srv.server_address[1]}: /fleetz (rollup), "
                  f"/fleetz/metrics (one-endpoint Prometheus scrape)",
                  file=sys.stderr)
        except OSError as e:
            print(f"[launch] could not bind --fleetz_port {fleetz_port}:"
                  f" {e}; fleet view disabled", file=sys.stderr)
        # fleet-wide push (ISSUE 15 satellite): ONE aggregated POST from
        # the coordinator per interval instead of N per-rank pushes —
        # the URL is consumed here so children never see it (per-rank
        # mode unchanged when fleet aggregation is not armed)
        push_url = os.environ.pop("PADDLE_METRICS_PUSH_URL", None)
        if push_url:
            from ..telemetry import export as _export

            fleet_exporter = _export.start_fleet(
                push_url, coord.fleet_status, coord.fleet_metrics,
                interval_s=float(os.environ.get(
                    "PADDLE_METRICS_PUSH_SECS", "15") or 15),
                retries=int(os.environ.get(
                    "PADDLE_METRICS_PUSH_RETRIES", "3") or 3))
            print(f"[launch] fleet metrics push -> {push_url} "
                  f"(aggregated; per-rank pushes suppressed)",
                  file=sys.stderr)

    # sharded-checkpoint commit barrier (fluid/checkpoint.py): every
    # multi-rank job gets one — it costs a daemon thread and only
    # matters once PADDLE_CKPT_SHARDED arms sharded saves in the
    # trainers. Lease-armed jobs reach it through the coordinator's
    # port (ckpt_* verbs delegate); otherwise the coordinator's barrier
    # object is served standalone
    ckpt_barrier_server = None
    if len(cluster) > 1:
        if durable_coord:
            # the barrier rides the durable coordinator's port(s): the
            # ORDERED endpoint list makes a mid-flight sharded
            # checkpoint survive a coordinator respawn or promotion
            os.environ["PADDLE_CKPT_BARRIER_ENDPOINT"] = coord_ep
        elif coord_server is not None:
            os.environ["PADDLE_CKPT_BARRIER_ENDPOINT"] = coord_ep
        else:
            from .coordinator import serve_ckpt_barrier

            ckpt_barrier_server, bar_ep = serve_ckpt_barrier(
                coord.ckpt_barrier)
            os.environ["PADDLE_CKPT_BARRIER_ENDPOINT"] = bar_ep

    pservers: List[PServer] = []
    ps_supervisor = None
    snapshot_dir = None
    own_snapshot_dir = False
    adopt_snapshots = False
    if args.ps_snapshot_mode:
        # pservers inherit it through _spawn_pserver's env copy
        os.environ["PADDLE_PS_SNAPSHOT_MODE"] = args.ps_snapshot_mode
    if args.ps_replication is not None:
        if args.ps_replication > 1:
            if args.servers:
                n_ps = len([e for e in args.servers.split(",")
                            if e.strip()])
            else:
                n_ps = args.server_num
            if n_ps < args.ps_replication:
                print(f"[launch] --ps_replication {args.ps_replication} "
                      f"needs at least that many pservers, got {n_ps} "
                      f"(--server_num / --servers)", file=sys.stderr)
                return 2
        # trainers inherit it through start_local_trainers' env copy;
        # RemoteTable reads it as the default replication factor
        os.environ["PADDLE_PS_REPLICATION"] = str(args.ps_replication)

    try:
        if args.server_num or args.servers:
            if snapshot_secs > 0:
                snapshot_dir = os.environ.get("PADDLE_PS_SNAPSHOT_DIR")
                if snapshot_dir:
                    # stable cross-job dir: a previous job's snapshots
                    # (+ manifest) are adopted on first spawn
                    adopt_snapshots = True
                else:
                    if args.log_dir:
                        snapshot_dir = os.path.join(
                            args.log_dir, "ps_snapshots")
                    else:
                        import tempfile

                        snapshot_dir = tempfile.mkdtemp(
                            prefix="paddle_tpu_ps_")
                        own_snapshot_dir = True
                os.makedirs(snapshot_dir, exist_ok=True)
            pservers, endpoints = start_pservers(
                args.server_num, args.servers, node_ip, args.log_dir,
                snapshot_dir=snapshot_dir, snapshot_secs=snapshot_secs,
                heartbeat_dir=heartbeat_dir,
                adopt_snapshots=adopt_snapshots)
            # trainers inherit the list through start_local_trainers' env
            os.environ["PADDLE_PSERVERS_IP_PORT_LIST"] = ",".join(endpoints)
            os.environ.setdefault("PADDLE_TRAINING_ROLE", "TRAINER")
            if args.elastic_retries > 0:
                ps_supervisor = PServerSupervisor(
                    pservers, args.elastic_retries, args.log_dir,
                    snapshot_dir, snapshot_secs,
                    heartbeat_dir=heartbeat_dir,
                    heartbeat_timeout=args.heartbeat_timeout)
        coord_supervisor = None
        if coord_children is not None:
            coord_supervisor = CoordinatorSupervisor(
                coord_children, args.elastic_retries,
                ledger=fleet_ledger)
        rc = _launch_attempts(args, ips, node_ip, cluster, heartbeat_dir,
                              ps_supervisor, grace, coord=coord,
                              lease_armed=lease_secs > 0,
                              fleet_ledger=fleet_ledger,
                              coord_supervisor=coord_supervisor)
        if args.trace_dir:
            # pservers dump their span timelines on SIGTERM — stop them
            # BEFORE the merge so timeline.json spans the whole job
            # (trainer ranks + pserver + coordinator lanes)
            terminate_pservers(pservers)
            pservers = []
            try:
                from ..telemetry import tracing as _tracing

                # the coordinator serves inside THIS process: its
                # renewal/election spans live in the launcher's ring
                _tracing.dump_chrome(directory=args.trace_dir,
                                     tag="coord")
                _tracing.flight_dump("exit", directory=args.trace_dir,
                                     tag="coord")
            except Exception:  # noqa: BLE001 — merge anyway
                pass
            from ..telemetry.timeline import merge_traces

            merged = merge_traces(args.trace_dir)
            if merged:
                print(f"[launch] merged timeline: {merged} (open in "
                      f"Perfetto / chrome://tracing)", file=sys.stderr)
        return rc
    finally:
        terminate_pservers(pservers)
        if fleet_exporter is not None:
            try:
                fleet_exporter.stop(final_flush=True)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if coord_server is not None:
            stop_coordinator(coord_server)
        if ckpt_barrier_server is not None:
            stop_coordinator(ckpt_barrier_server)  # same teardown shape
        if coord_children is not None:
            # SIGTERM = graceful: the coordinator writes a final
            # snapshot, so a follow-up job adopting the state dir
            # restarts lossless
            for ent in coord_children.values():
                p = ent.get("proc")
                if p is not None and p.poll() is None:
                    p.terminate()
            for ent in coord_children.values():
                p = ent.get("proc")
                if p is not None:
                    try:
                        p.wait(timeout=5)
                    except subprocess.TimeoutExpired:
                        p.kill()
            try:
                coord.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if own_coord_state:
            import shutil

            shutil.rmtree(coord_state_root, ignore_errors=True)
        if own_heartbeat_dir:
            import shutil

            shutil.rmtree(heartbeat_dir, ignore_errors=True)
        if own_snapshot_dir:
            import shutil

            shutil.rmtree(snapshot_dir, ignore_errors=True)


def _launch_attempts(args, ips, node_ip, cluster, heartbeat_dir,
                     ps_supervisor=None, grace=None, coord=None,
                     lease_armed=False, fleet_ledger=None,
                     coord_supervisor=None) -> int:
    """Supervision loop with per-rank budgets and elastic resize.

    Failure accounting lives in the coordinator: every group-ending
    trainer failure (nonzero exit, stale heartbeat, expired lease,
    straggler ejection) is charged to THAT member's per-rank budget
    (coordinator.report_failure). Within budget, the group restarts at
    the same world size (the sync-PS barrier demands a group restart
    either way); past budget the member is EVICTED — the membership
    epoch bumps and the survivors restart at world-1 from the last
    checkpoint (PADDLE_ELASTIC_RESHARD=1 is exported so their
    CheckpointManagers accept the resized resume). --elastic_retries
    stays the JOB-LEVEL restart cap."""
    debugz_base = args.debugz_port
    if debugz_base is None:
        raw = os.environ.get("PADDLE_DEBUGZ_PORT")
        if raw:
            try:
                debugz_base = int(raw)
            except ValueError:
                debugz_base = None
    # serving mode: each rank is one inference replica; the positional
    # arg is the model dir, extra args pass through to the server
    serve_module = None
    serve_args: List[str] = []
    if getattr(args, "serve", False):
        serve_module = "paddle_tpu.inference.server"
        serve_args = (["--model_dir", args.training_script]
                      + list(args.training_script_args))
        # KV-pool knobs ride the env protocol into every replica (the
        # same PADDLE_SERVE_* envs an operator would set by hand)
        if getattr(args, "serve_kv_cache", None) is not None:
            os.environ["PADDLE_SERVE_KV_CACHE"] = args.serve_kv_cache
        if getattr(args, "serve_kv_pages", None) is not None:
            os.environ["PADDLE_SERVE_KV_PAGES"] = str(
                args.serve_kv_pages)
        print(f"[launch] serving replicas: "
              f"{','.join(t.endpoint for t in cluster)}",
              file=sys.stderr)
    elastic_enabled = (args.elastic_retries > 0
                       or args.elastic_retries_per_rank is not None)
    # job-level cap: --elastic_retries when given; with only per-rank
    # budgets, a generous derived bound (every rank exhausting its own
    # budget plus its eviction restart)
    per_rank = (args.elastic_retries_per_rank
                if args.elastic_retries_per_rank is not None
                else args.elastic_retries)
    job_cap = (args.elastic_retries if args.elastic_retries > 0
               else (per_rank + 1) * len(cluster))
    trainers = list(cluster)  # survivors, re-ranked on resize
    attempt = 0
    epoch = coord.epoch if coord is not None else 0
    # goodput lifecycle (ISSUE 15): one `restart` event per group
    # respawn, carrying detect_ts (watch noticed the death) and
    # respawn_ts (replacement group spawned) — goodtop decomposes each
    # cross-incarnation gap against these
    pending_restart = None
    while True:
        local = start_local_trainers(
            trainers, node_ip, args.training_script,
            serve_args if serve_module else args.training_script_args,
            args.log_dir, restart_count=attempt,
            heartbeat_dir=heartbeat_dir, debugz_base_port=debugz_base,
            membership_epoch=epoch, module=serve_module,
        )
        if pending_restart is not None:
            pending_restart["respawn_ts"] = round(time.time(), 6)
            if fleet_ledger is not None:
                fleet_ledger.event(event="restart", **pending_restart)
            if coord is not None:
                coord.note_incident(
                    dict(pending_restart, event="restart"))
            pending_restart = None
        if not local:
            print(f"[launch] node_ip {node_ip} not in --ips {ips}", file=sys.stderr)
            return 2
        if grace is not None:
            grace.trainers = local
        if coord is not None and lease_armed:
            for t in local:
                coord.register(t.tag, kind="trainer", endpoint=t.endpoint)
        monitor = None
        if heartbeat_dir and args.heartbeat_timeout > 0:
            from .heartbeat import HeartBeatMonitor

            # created AFTER spawn: a fresh monitor ignores stamps older
            # than itself, so leftovers from a previous attempt/job in a
            # reused shared dir never read as hangs; it knows the
            # membership epoch so a future-epoch stamp (a member owned
            # by a NEWER coordinator) is never read as proof of life
            monitor = HeartBeatMonitor(
                heartbeat_dir, [t.rank for t in local],
                args.heartbeat_timeout, epoch=epoch,
            )
        straggler = None
        eject = args.straggler_eject_factor > 0
        if heartbeat_dir and (args.straggler_factor > 0 or eject):
            from .heartbeat import StragglerMonitor

            straggler = StragglerMonitor(
                heartbeat_dir, [t.rank for t in local],
                factor=(args.straggler_eject_factor
                        if eject else args.straggler_factor))
        serve_respawner = None
        if serve_module is not None and elastic_enabled:
            serve_respawner = ServeRespawner(
                trainers, node_ip, args.training_script, serve_args,
                args.log_dir, retries=per_rank,
                heartbeat_dir=heartbeat_dir, debugz_base_port=debugz_base,
                membership_epoch=epoch, module=serve_module)
        failure: dict = {}
        rc = watch_local_trainers(
            local, monitor=monitor, ps_supervisor=ps_supervisor,
            grace=grace, straggler=straggler, failure=failure,
            coordinator=coord if lease_armed else None,
            straggler_eject=eject, serve_respawner=serve_respawner,
            fleet_ledger=fleet_ledger, incident_coord=coord,
            coord_supervisor=coord_supervisor)
        detect_ts = time.time()  # the watch just noticed the death
        if (rc == 0
                or rc == 128 + signal.SIGINT
                or rc == 128 + signal.SIGTERM  # whole-job preemption
                or (ps_supervisor is not None and ps_supervisor.aborted)
                or not elastic_enabled):
            return rc
        # charge the failure to the culprit's per-rank budget; the
        # coordinator decides restart-in-place vs evict-and-resize
        tag = failure.get("tag", local[0].tag)
        rank = failure.get("rank", "?")
        reason = failure.get("reason", f"exit code {rc}")
        resized = False
        if coord is not None:
            verdict = coord.report_failure(tag, reason)
            if verdict["evicted"]:
                new_world = len(trainers) - 1
                if new_world < max(1, args.min_world_size):
                    print(f"[launch] {tag} (rank {rank}) exhausted its "
                          f"per-rank budget ({reason}) and the job "
                          f"cannot resize below "
                          f"--min_world_size={args.min_world_size}; "
                          f"aborting", file=sys.stderr)
                    return rc
                if len(ips) > 1:
                    print(f"[launch] {tag} (rank {rank}) exhausted its "
                          f"per-rank budget ({reason}); elastic resize "
                          f"is single-node only — aborting",
                          file=sys.stderr)
                    return rc
                survivors = [t for t in trainers if t.tag != tag]
                # re-rank 0..W-1 but keep each survivor's stable tag
                # (and endpoint — ports are identity on CPU fleets)
                trainers = [Trainer(i, t.endpoint, tag=t.tag)
                            for i, t in enumerate(survivors)]
                epoch = verdict["epoch"]
                resized = True
        if attempt >= job_cap:
            print(f"[launch] {tag} (rank {rank}) failed ({reason}) and "
                  f"the job-level restart cap ({job_cap}) is exhausted; "
                  f"aborting", file=sys.stderr)
            return rc
        attempt += 1
        pending_restart = {
            "tag": tag, "rank": rank, "reason": reason,
            "detect_ts": round(detect_ts, 6), "attempt": attempt,
            "world": len(trainers), "resized": resized,
        }
        if resized:
            # elastic resize: survivors re-shard their checkpoints
            # (CheckpointManager world-size gate) and the sync-PS
            # barrier adopts the new trainer_num via the generation bump
            os.environ["PADDLE_ELASTIC_RESHARD"] = "1"
            print(
                f"[launch] elastic restart {attempt}/{job_cap}: {tag} "
                f"(rank {rank}) evicted after {reason}; membership "
                f"epoch {epoch}, resizing to world_size="
                f"{len(trainers)} (survivors resume from checkpoint, "
                f"re-sharded)",
                file=sys.stderr,
            )
        else:
            print(
                f"[launch] elastic restart {attempt}/{job_cap}: {tag} "
                f"(rank {rank}) died ({reason}); group restarts at "
                f"world_size={len(trainers)} (trainers resume from "
                f"checkpoint)",
                file=sys.stderr,
            )
        if heartbeat_dir:
            # drop stale stamps so the new group starts with a clean slate
            from .heartbeat import _stamp_path

            for t in local:
                try:
                    os.remove(_stamp_path(heartbeat_dir, t.rank))
                except OSError:
                    pass


if __name__ == "__main__":
    sys.exit(launch())
