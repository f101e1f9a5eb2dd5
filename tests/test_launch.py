"""Launcher (reference distributed/launch.py + utils.watch_local_trainers):
spawn with the env protocol, collect, abort-all on child failure."""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_launch(tmp_path, script_body, nproc=3, extra=()):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(script_body))
    cmd = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--nproc_per_node", str(nproc),
        "--log_dir", str(tmp_path / "logs"),
        *extra,
        str(script), str(tmp_path),
    ]
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_launch_env_protocol(tmp_path):
    r = _run_launch(
        tmp_path,
        """
        import os, sys
        out = sys.argv[1]
        rank = os.environ["PADDLE_TRAINER_ID"]
        with open(os.path.join(out, f"rank{rank}.txt"), "w") as f:
            f.write("|".join([
                rank,
                os.environ["PADDLE_TRAINERS_NUM"],
                os.environ["PADDLE_TRAINER_ENDPOINTS"],
                os.environ["PADDLE_CURRENT_ENDPOINT"],
            ]))
        """,
        nproc=3,
    )
    assert r.returncode == 0, r.stderr
    seen = set()
    for rank in range(3):
        txt = (tmp_path / f"rank{rank}.txt").read_text().split("|")
        assert txt[0] == str(rank)
        assert txt[1] == "3"
        eps = txt[2].split(",")
        assert len(eps) == 3 and txt[3] in eps
        seen.add(txt[3])
    assert len(seen) == 3  # unique ports
    # logs captured per worker
    assert sorted(os.listdir(tmp_path / "logs")) == [
        "workerlog.0", "workerlog.1", "workerlog.2"
    ]


def test_launch_aborts_all_on_failure(tmp_path):
    r = _run_launch(
        tmp_path,
        """
        import os, sys, time
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        out = sys.argv[1]
        if rank == 1:
            sys.exit(7)  # fail fast
        # other ranks would run "forever"; the launcher must kill them
        for _ in range(600):
            time.sleep(0.1)
        with open(os.path.join(out, f"survived{rank}"), "w") as f:
            f.write("should not happen")
        """,
        nproc=3,
    )
    assert r.returncode == 7, (r.returncode, r.stderr)
    assert "aborting the job" in r.stderr
    assert not any(p.name.startswith("survived") for p in tmp_path.iterdir())


def test_launch_unknown_node_ip(tmp_path):
    r = _run_launch(
        tmp_path,
        "import sys\n",
        nproc=1,
        extra=("--ips", "10.1.1.1,10.1.1.2", "--node_ip", "10.9.9.9"),
    )
    assert r.returncode == 2


def test_launch_elastic_restart_recovers(tmp_path):
    """Rank 0 crashes on the first attempt, succeeds after the elastic
    restart (PADDLE_ELASTIC_RESTART carries the attempt number) — the
    automated form of the reference's checkpoint+restart recovery story."""
    r = _run_launch(
        tmp_path,
        """
        import os, sys
        out = sys.argv[1]
        rank = os.environ["PADDLE_TRAINER_ID"]
        attempt = int(os.environ["PADDLE_ELASTIC_RESTART"])
        with open(os.path.join(out, f"attempts.{rank}.{attempt}"), "w"):
            pass
        if rank == "0" and attempt == 0:
            sys.exit(3)  # simulated crash before the first checkpoint
        """,
        nproc=2,
        extra=("--elastic_retries", "2"),
    )
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "attempts.0.0").exists()
    assert (tmp_path / "attempts.0.1").exists()  # restarted group ran
    assert "elastic restart 1/2" in r.stderr


def test_launch_elastic_exhausted_fails(tmp_path):
    r = _run_launch(
        tmp_path,
        """
        import sys
        sys.exit(7)
        """,
        nproc=2,
        extra=("--elastic_retries", "1"),
    )
    assert r.returncode == 7
    assert "elastic restart 1/1" in r.stderr


def test_launch_heartbeat_detects_hang(tmp_path):
    """A trainer that stops heartbeating (hung collective analog) is
    detected and the group is torn down with exit code 124 — capability
    the reference lacks (its launcher only sees hard exits)."""
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import os, sys, time
        sys.path.insert(0, os.environ["REPO"])
        from paddle_tpu.distributed.heartbeat import start_heartbeat
        rank = os.environ["PADDLE_TRAINER_ID"]
        hb = start_heartbeat(interval=0.2)
        assert hb is not None
        if rank == "1":
            hb.stop()   # rank 1 "hangs": alive but no heartbeats
            time.sleep(60)
        else:
            time.sleep(60)  # healthy ranks keep beating while they work
        """
    ))
    cmd = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--nproc_per_node", "2", "--heartbeat_timeout", "2.0",
        str(script),
    ]
    env = dict(os.environ, PYTHONPATH=REPO, REPO=REPO,
               PADDLE_HEARTBEAT_DIR=str(hb_dir))
    t0 = time.time()
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 124, (r.returncode, r.stderr)
    assert "stopped heartbeating" in r.stderr
    assert time.time() - t0 < 45  # detected the hang, did not wait out sleeps


def test_launch_heartbeat_ignores_clean_exit_and_stale_leftovers(tmp_path):
    """A rank that exits 0 stops stamping but must not read as hung; a
    leftover stamp from a previous job in a reused dir must not kill the
    new group (monitor only trusts stamps newer than itself)."""
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir()
    # leftover stamp from a "previous job", hours old
    stale = hb_dir / "heartbeat.0"
    stale.write_text("0.0")
    os.utime(stale, (1, 1))
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import os, sys, time
        sys.path.insert(0, os.environ["REPO"])
        from paddle_tpu.distributed.heartbeat import start_heartbeat
        start_heartbeat(interval=0.2)
        rank = os.environ["PADDLE_TRAINER_ID"]
        if rank == "0":
            time.sleep(1)   # finishes early, exits 0, stops stamping
        else:
            time.sleep(8)   # keeps working well past rank 0's staleness
        """
    ))
    cmd = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--nproc_per_node", "2", "--heartbeat_timeout", "2.0",
        str(script),
    ]
    env = dict(os.environ, PYTHONPATH=REPO, REPO=REPO,
               PADDLE_HEARTBEAT_DIR=str(hb_dir))
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, (r.returncode, r.stderr)


def test_launch_straggler_drill_logs_structured_event(tmp_path):
    """Telemetry (ISSUE 4): a deliberately slow rank must produce one
    structured `straggler` JSON event in the launcher log — step rates
    ride the heartbeat stamps (fluid/monitor.py publishes them; here the
    worker fakes the provider so the drill needs no jax import) and the
    job is NOT killed (diagnosis, not enforcement)."""
    hb_dir = tmp_path / "hb"
    hb_dir.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import json, os, sys, time
        sys.path.insert(0, os.environ["REPO"])
        from paddle_tpu.distributed import heartbeat
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        step = [0]
        heartbeat.set_step_provider(lambda: (step[0], None))
        hb = heartbeat.start_heartbeat(interval=0.1)
        done = os.environ["DRILL_SLOW_RANK_DONE"]
        if rank == 0:
            # the fast rank steps for as long as the slow one lives (not
            # for a fixed 24 x 0.02 s, which a loaded host's launcher can
            # miss between two polls): the detector needs two samples of
            # a peer's progress before it can call anyone slow
            deadline = time.time() + 90
            while not os.path.exists(done) and time.time() < deadline:
                time.sleep(0.02)
                step[0] += 1
        else:
            for _ in range(24):
                time.sleep(0.25)  # drags >10x
                step[0] += 1
            time.sleep(0.3)  # one more beat with the final count
            open(done, "w").close()
        hb.stop()
        """
    ))
    cmd = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--nproc_per_node", "2", "--straggler_factor", "3.0",
        str(script),
    ]
    env = dict(os.environ, PYTHONPATH=REPO, REPO=REPO,
               PADDLE_HEARTBEAT_DIR=str(hb_dir),
               DRILL_SLOW_RANK_DONE=str(tmp_path / "slow_rank_done"))
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, (r.returncode, r.stderr)
    events = []
    for line in r.stderr.splitlines():
        if line.startswith("[telemetry] "):
            events.append(json.loads(line[len("[telemetry] "):]))
    stragglers = [e for e in events if e.get("event") == "straggler"]
    assert stragglers, r.stderr
    assert all(str(e["rank"]) == "1" for e in stragglers)
    ev = stragglers[0]
    assert ev["step_time_ms"] > 3 * ev["median_step_time_ms"]


def test_launch_trace_dir_merges_per_rank_timeline(tmp_path):
    """--trace_dir: each rank auto-dumps its host-span chrome trace
    (PADDLE_TRACE_DIR contract) and the launcher merges them into one
    timeline.json with per-rank pids."""
    trace_dir = tmp_path / "traces"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import os, sys, time
        sys.path.insert(0, os.environ["REPO"])
        from paddle_tpu.fluid import profiler
        assert profiler.maybe_start_trace_collection()
        with profiler.RecordEvent("unit_of_work"):
            time.sleep(0.05)
        """
    ))
    cmd = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--nproc_per_node", "2", "--trace_dir", str(trace_dir),
        str(script),
    ]
    env = dict(os.environ, PYTHONPATH=REPO, REPO=REPO)
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, (r.returncode, r.stderr)
    assert "merged timeline" in r.stderr
    merged = trace_dir / "timeline.json"
    assert merged.exists()
    evs = json.load(open(merged))["traceEvents"]
    spans = [e for e in evs if e["name"] == "unit_of_work"]
    # one span per rank, under per-rank pid namespaces
    assert {e["pid"] // 100 for e in spans} == {0, 1}
    names = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert any(n.startswith("rank 0") for n in names)
    assert any(n.startswith("rank 1") for n in names)


def test_refuses_several_processes_on_a_chip_host(monkeypatch):
    """One process per chip host: on the v5e machine the second of two
    --serve replicas died at backend init and was respawned until the
    budget ran out (PR 21 chip run), so the launcher refuses up front."""
    from paddle_tpu.distributed import launch

    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: ["/dev/vfio/2"])
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    launch._check_one_process_per_chip_host(1)
    with pytest.raises(launch.ChipOwnershipError, match="ONE process per host"):
        launch.launch(["--serve", "--nproc_per_node", "2", "model_dir"])
    # a CPU fleet on the same host never opens a chip
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    launch._check_one_process_per_chip_host(2)
