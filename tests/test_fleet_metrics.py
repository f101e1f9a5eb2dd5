"""fleet.metrics — allreduced scalar metric helpers
(reference python/paddle/fleet/metrics/metric.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import fleet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_metrics_worker.py")


def _exact_auc(scores, labels):
    """Pairwise-comparison AUC oracle (probability a random positive
    scores above a random negative, ties count half)."""
    pos = scores[labels == 1]
    neg = scores[labels == 0]
    wins = (pos[:, None] > neg[None, :]).sum()
    ties = (pos[:, None] == neg[None, :]).sum()
    return (wins + 0.5 * ties) / (len(pos) * len(neg))


def test_single_process_identity_and_resolution():
    """world=1: reduce is the identity; Variable/str resolve from scope."""
    arr = np.asarray([3.0, 4.0], np.float32)
    np.testing.assert_allclose(fleet.metrics.sum(arr), arr)
    np.testing.assert_allclose(fleet.metrics.max(arr), arr)
    np.testing.assert_allclose(fleet.metrics.min(arr), arr)
    assert fleet.metrics.acc(np.asarray([30.0]), np.asarray([40.0])) == 0.75
    assert fleet.metrics.mae(np.asarray([5.0]), 10) == 0.5
    assert fleet.metrics.mse(np.asarray([90.0]), 10) == 9.0
    assert fleet.metrics.rmse(np.asarray([90.0]), 10) == 3.0

    scope = fluid.executor.Scope()
    scope.set_var("m", np.asarray([7.0], np.float32))
    np.testing.assert_allclose(fleet.metrics.sum("m", scope=scope), [7.0])
    with pytest.raises(KeyError):
        fleet.metrics.sum("nope", scope=scope)

    prog = fluid.Program()
    with fluid.program_guard(prog):
        v = fluid.layers.data("v", [1], append_batch_size=False)
    with fluid.scope_guard(scope):
        scope.set_var("v", np.asarray([9.0], np.float32))
        np.testing.assert_allclose(fleet.metrics.sum(v), [9.0])


def test_auc_matches_pairwise_oracle():
    """Bucket-integrated AUC (the reference's loop, vectorized) against
    the exact pairwise definition on the same bucketization."""
    rng = np.random.RandomState(0)
    scores = rng.rand(2000)
    labels = (rng.rand(2000) < scores).astype(int)  # informative scores

    nb = 4096
    bucket = np.minimum((scores * nb).astype(int), nb - 1)
    pos = np.bincount(bucket[labels == 1], minlength=nb).astype(float)
    neg = np.bincount(bucket[labels == 0], minlength=nb).astype(float)

    got = fleet.metrics.auc(pos, neg)
    want = _exact_auc(bucket, labels)  # same quantization as the buckets
    np.testing.assert_allclose(got, want, rtol=1e-9)
    assert 0.5 < got < 1.0  # informative scores beat chance


def test_auc_degenerate_returns_half():
    z = np.zeros(16)
    assert fleet.metrics.auc(z, z) == 0.5
    assert fleet.metrics.auc(np.ones(16), z) == 0.5  # no negatives


def test_auc_2d_stats_accepted():
    """layers.auc emits [1, num_thresholds] stats — accepted like the
    reference's global_pos[0] indexing."""
    pos = np.asarray([[0.0, 2.0, 1.0]])
    neg = np.asarray([[3.0, 1.0, 0.0]])
    a2 = fleet.metrics.auc(pos, neg)
    a1 = fleet.metrics.auc(pos[0], neg[0])
    assert a2 == a1


def test_two_process_parity(tmp_path):
    """2 launcher processes with different local stats: every helper
    must return the globally-merged value, identical on both ranks."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PADDLE_DIST_TRACE_DIR"] = str(tmp_path)
    env["PYTHONPATH"] = REPO

    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        port = s.getsockname()[1]
    r = subprocess.run(
        [sys.executable, "-u", "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--started_port", str(port), WORKER],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO)
    assert r.returncode == 0, f"rc={r.returncode}:\n{r.stdout}\n{r.stderr}"

    m0 = json.load(open(tmp_path / "metrics.0.json"))
    m1 = json.load(open(tmp_path / "metrics.1.json"))
    assert m0 == m1, "ranks must agree on every global metric"

    # oracle: the numpy-combined stats (rank 0: [1.5, 2.0]; rank 1: [2.5, 4.0])
    np.testing.assert_allclose(m0["sum"], [4.0, 6.0])
    np.testing.assert_allclose(m0["max"], [2.5, 4.0])
    np.testing.assert_allclose(m0["min"], [1.5, 2.0])
    # acc = (10 + 15) / (20 + 20)
    np.testing.assert_allclose(m0["acc"], 25.0 / 40.0)
    # mae = (6 + 7) / 10
    np.testing.assert_allclose(m0["mae"], 1.3)
    # auc over SUMMED buckets (replicate the worker's draw order: pos
    # then neg from one per-rank stream)
    p = np.zeros(8)
    n = np.zeros(8)
    for rank in range(2):
        rng = np.random.RandomState(rank)
        p += rng.randint(0, 50, (8,)).astype(np.float64)
        n += rng.randint(0, 50, (8,)).astype(np.float64)
    np.testing.assert_allclose(m0["auc"], fleet.metrics.auc(p, n))
