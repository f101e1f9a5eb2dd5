"""run.py end to end on the CPU: every cell's rehearsal in a process of its
own (four virtual devices for the four-chip cell), the refusal to measure
without a TPU, and the program against the plain reference in float32."""
import json
import os
import subprocess
import sys
from unittest import mock

import pytest

from benchmark import harness, manifest

RUN = os.path.join(manifest.BENCH_DIR, "run.py")
CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]
TRACED = {"bert-base.s512.dp4"}  # one rehearsal takes the traced path


def _start(args):
    return subprocess.Popen(
        [sys.executable, RUN] + args, cwd=manifest.ROOT, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)


@pytest.fixture(scope="module")
def rehearsals():
    """All cells at once: each is a process of its own, as on the chip."""
    started = {
        name: _start(["--workload", name, "--rehearse", "--seed", "3",
                      "--seconds", "1", "--trace", str(int(name in TRACED))])
        for name in CELLS}
    out = {}
    for name, proc in started.items():
        stdout, stderr = proc.communicate(timeout=600)
        out[name] = (proc.returncode, stdout, stderr)
    return out


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_prints_the_contract_line(rehearsals, name):
    code, stdout, stderr = rehearsals[name]
    assert code == 0, stderr[-3000:]
    lines = stdout.strip().splitlines()
    result = json.loads(lines[-1])
    doc = manifest.load_manifest()
    cell = manifest.load_cell(doc, name, rehearse=True)
    traced = name in TRACED

    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True
    assert result["failed"] == 0
    log_every = cell.traffic["log_every"]
    assert result["attempted"] > 0 and result["attempted"] % log_every == 0
    device = result["device"]
    assert (device["platform"], device["count"]) == ("cpu", cell.chips)
    assert set(device) == {"platform", "kind", "count", "memory_peak_bytes"} | (
        {"busy_s", "window_s"} if traced else set())

    rows = cell.per_layer if traced else cell.end_to_end
    units = {m["name"]: m["unit"] for m in rows}
    counters = {m["name"] for m in rows if m["source"] == "program_counter"}
    for metric, got in result["metrics"].items():
        assert got["unit"] == units[metric]
        # a count is a count anywhere; a time, rate or utilization read on
        # the CPU is not a device number and is printed as null
        assert (got["value"] is None) == (metric not in counters), metric
    if traced:
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert device["busy_s"] is None and device["window_s"] is None
        # device-trace metrics find no device plane and are left out
        assert "collective_ms_per_step" not in result["metrics"]
        assert "program_build_s" in result["metrics"]
    else:
        assert set(result["metrics"]) == set(units)
        assert "setup_s" in units and len(units) >= 2

    details = json.loads(lines[-2])["details"]
    assert details["setup_split_s"] is None
    assert details["group_seconds"] is None
    check = details["check"]
    assert check["ok"] and check["loss_falls"]
    assert len(check["grad_rel_l2_error"]) >= 3
    for label, limit in check["tolerance"]["grad_rel_l2"].items():
        assert limit is None or check["grad_rel_l2_error"][label] <= limit
    assert details["moved_in_window"]["jax_compile_requests"] == 0
    if cell.mesh_axes:
        shards = details["structure"]["feed_shard_shapes"]
        assert shards["input_ids"] == [cell.traffic["batch"] // 4,
                                       cell.traffic["seq_len"]]


def test_without_a_tpu_nothing_is_measured():
    proc = _start(["--workload", "bert-base.s512", "--seed", "0",
                   "--seconds", "1", "--trace", "0"])
    stdout, stderr = proc.communicate(timeout=300)
    assert proc.returncode != 0
    assert stdout.strip() == ""
    assert "measured on a TPU" in stderr
    proc = _start(["--workload", "no.such.cell", "--rehearse"])
    stdout, stderr = proc.communicate(timeout=300)
    assert proc.returncode != 0 and stdout.strip() == ""
    assert "no workload 'no.such.cell'" in stderr


@pytest.mark.parametrize("name", ["bert-base.s4096", "resnet50.train224"])
def test_program_is_the_reference_in_float32(name):
    """With the AMP rewrite left out, the program built by the harness and
    the family's plain reference are the same arithmetic: loss and
    gradients agree to float32 rounding, where bf16 AMP sits at 1e-2.
    This is what makes the chip's comparison a test of precision and of
    the kernels, and not of two different models."""
    from paddle_tpu.contrib import mixed_precision

    cell = manifest.load_cell(manifest.load_manifest(), name, rehearse=True)
    with mock.patch.object(mixed_precision, "decorate",
                           lambda opt, use_bf16=True: opt):
        check = harness.run_check(cell, seed=5)
    assert check["loss_rel_error"] < 1e-5
    assert max(check["grad_rel_l2_error"].values()) < 1e-4
    assert check["loss_falls"]
