"""`BENCHMARK.json` against the files it names, and the traffic generators."""
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, manifest

CONTRACT_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def doc():
    return manifest.load_manifest()


def test_manifest_names_files_that_exist_and_agree(doc):
    assert set(doc) == CONTRACT_KEYS
    assert manifest.problems(doc) == []
    assert 1 <= doc["run_seconds"] <= 51
    # the command names no file outside `paths`
    assert doc["command"][1].startswith(doc["paths"][0] + "/")
    for cfg in doc["configs"]:
        assert cfg["file"].startswith(doc["paths"][0] + "/")
        on_disk = manifest.load_json(os.path.join(manifest.ROOT, cfg["file"]))
        assert on_disk["reduced"] == cfg["reduced"]
        # `reduced` never names a width
        for key in cfg["reduced"]:
            assert not (key.endswith(("_dim", "_rank", "_size"))
                        or "head" in key or "expert" in key), key
    for name in os.listdir(os.path.join(manifest.BENCH_DIR, "layer_metrics")):
        assert name[:-3] in {m["name"] for m in doc["per_layer"]}, name
    for m in doc["end_to_end"]:
        assert manifest.load_module("end_to_end", m["name"]) is not None
    # every plain file name: letters, digits, _ . - /
    for base in doc["paths"]:
        for folder, _, files in os.walk(os.path.join(manifest.ROOT, base)):
            if "__pycache__" in folder:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), manifest.ROOT)
                assert all(c.isalnum() or c in "_.-/" for c in rel), rel


@pytest.mark.parametrize("break_it, finds", [
    (lambda d: d["workloads"].append(
        dict(d["workloads"][0], name="extra.dp4", chips=4, traffic="none")),
     ["no traffic file", "cells ask for four chips"]),
    (lambda d: d["per_layer"][0].update(moves="no_such_metric"),
     ["moves no end-to-end metric", "moves is 'no_such_metric'"]),
    (lambda d: d["per_layer"].append(
        dict(d["per_layer"][-1], name="flash_ms_per_step2", workloads=[
            "resnet50.train224"], moves="tokens_per_s_per_chip")),
     ["no reader file"]),
    (lambda d: next(m for m in d["per_layer"]
                    if m["name"] == "flash_ms_per_step").update(
        workloads=["resnet50.train224"]),
     ["is reported in 'resnet50.train224', where 'tokens_per_s_per_chip' is "
      "not"]),
    (lambda d: d["workloads"][1].update(name="bad name!"), ["bad name"]),
    (lambda d: d["end_to_end"][0].update(bound=0.2), ["bound 0.2"]),
])
def test_problems_are_found(doc, break_it, finds):
    broken = copy.deepcopy(doc)
    break_it(broken)
    found = "\n".join(manifest.problems(broken))
    for sentence in finds:
        assert sentence in found, found


def test_a_quarter_of_the_cells_may_take_four_chips(doc):
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    for w in four:  # only what exists across chips: the cell has a mesh
        assert manifest.load_cell(doc, w["name"]).mesh_axes


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bert_cell(doc):
    return manifest.load_cell(doc, "bert-base.s512")


def _bert_batch(cell, seed, batch=512):
    return cell.family.make_batch(cell.config, cell.traffic, batch,
                                  harness.batch_rng(seed, 1, 0))


def test_generator_is_a_function_of_the_seed(doc, bert_cell):
    a, b, c = (_bert_batch(bert_cell, s, 16) for s in (7, 7, 8))
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["input_ids"], c["input_ids"])
    # the pool's batches differ from each other
    d = bert_cell.family.make_batch(bert_cell.config, bert_cell.traffic, 16,
                                    harness.batch_rng(7, 1, 1))
    assert not np.array_equal(a["input_ids"], d["input_ids"])
    images = manifest.load_cell(doc, "resnet50.train224")
    x, y = (images.family.make_batch(images.config, images.traffic, 2,
                                     harness.batch_rng(3, 1, 0))
            for _ in range(2))
    assert np.array_equal(x["image"], y["image"])
    assert x["image"].shape == (2, 3, 224, 224)
    assert x["image"].dtype == np.float32 and x["label"].dtype == np.int64
    assert 0 <= x["label"].min() and x["label"].max() < 1000


def test_one_sequence_in_ten_is_short(bert_cell):
    batch = _bert_batch(bert_cell, 0, batch=4096)
    lengths = batch["input_mask"].sum(axis=1)
    short = lengths < 512
    assert short.mean() == pytest.approx(0.1, abs=0.02)
    assert lengths.min() >= 2
    # a short length is uniform in [2, S]: its mean is near S / 2
    assert lengths[short].mean() == pytest.approx(257, abs=25)


def test_masks_are_consistent(bert_cell):
    b, s, mp = 512, 512, 76
    batch = _bert_batch(bert_cell, 1, b)
    mask = batch["input_mask"]
    lengths = mask.sum(axis=1).astype(int)
    assert mask.dtype == np.float32 and set(np.unique(mask)) <= {0.0, 1.0}
    # ones then zeros; ids and segments are 0 on the padding
    assert (np.diff(mask, axis=1) <= 0).all()
    assert (batch["input_ids"][mask == 0] == 0).all()
    assert (batch["token_type_ids"][mask == 0] == 0).all()
    # two segments: 0 then 1, both non-empty
    types = batch["token_type_ids"]
    assert (np.diff(np.where(mask == 1, types, 1), axis=1) >= 0).all()
    assert (types[:, 0] == 0).all()
    assert (types[np.arange(b), lengths - 1] == 1).all()
    assert (batch["position_ids"] == np.arange(s)).all()

    weights = batch["mask_weights"].reshape(b, mp)
    pos = batch["mask_positions"].reshape(b, mp) - np.arange(b)[:, None] * s
    labels = batch["mask_labels"].reshape(b, mp)
    n_pred = weights.sum(axis=1).astype(int)
    want = np.minimum(np.clip(np.rint(lengths * 0.15), 1, mp), lengths - 1)
    assert (n_pred == want).all()
    assert n_pred.max() == mp  # 15 % of 512 is 77: the cap binds
    for row in range(b):
        used = pos[row, :n_pred[row]]
        assert (weights[row, :n_pred[row]] == 1).all()
        assert (used >= 1).all() and (used < lengths[row]).all()
        assert len(set(used)) == len(used) and (np.diff(used) > 0).all()
        # unused slots: weight 0, the row's own position 0, label 0
        assert (pos[row, n_pred[row]:] == 0).all()
        assert (labels[row, n_pred[row]:] == 0).all()
    assert batch["mask_positions"].dtype == np.int32
    assert set(np.unique(batch["nsp_labels"])) == {0, 1}


def test_flop_formulas_against_hand_values(doc, bert_cell):
    # BERT-base: 85.0 M matmul parameters in the stack + 23.4 M tied
    # embedding; 6 N + 12 L S H a token
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 30522 * 768
    per_token = 6 * n + 12 * 12 * 512 * 768
    assert bert_cell.family.step_flops(
        bert_cell.config, bert_cell.traffic, 64) == per_token * 64 * 512
    assert per_token * 64 * 512 / 1e12 == pytest.approx(23.2, abs=0.1)
    images = manifest.load_cell(doc, "resnet50.train224")
    flops = images.family.step_flops(images.config, images.traffic, 1)
    # ResNet-50: ~4.1 G multiply-adds forward an image (v1.5), x2 FLOPs, x3
    assert flops / 6e9 == pytest.approx(4.1, abs=0.1)
    from paddle_tpu.models.resnet import ResNetConfig, resnet_step_flops

    assert flops == resnet_step_flops(ResNetConfig.resnet50(), 1, 224)


# ---------------------------------------------------------------------------
# data-driven: new files and entries, no code edited
# ---------------------------------------------------------------------------


def test_a_dropped_in_cell_traffic_and_metric_are_found(tmp_path, doc):
    """A later PR adds a traffic file, a per-layer metric file and their
    entries; run.py of that tree finds them by name."""
    root = str(tmp_path)
    shutil.copytree(manifest.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = copy.deepcopy(doc)
    traffic = manifest.load_json(os.path.join(
        manifest.BENCH_DIR, "traffic", "imagenet-b256.json"))
    traffic["batch"] = 128
    traffic["rehearsal"]["log_every"] = 3
    with open(os.path.join(root, "benchmark", "traffic",
                           "imagenet-b128.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "groups_in_window.py"), "w") as f:
        f.write('"""Groups the window held."""\n'
                'LAYER = "step"\nMOVES = "step_ms"\nUNIT = "count"\n'
                'SOURCE = "program_counter"\n\n\n'
                "def read(run):\n    return float(len(run.groups))\n")
    new["workloads"].append({
        "name": "resnet50.b128", "config": "resnet50",
        "traffic": "imagenet-b128", "chips": 1, "why": "dropped in"})
    new["per_layer"].append({
        "name": "groups_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "step", "moves": "step_ms",
        "workloads": ["resnet50.b128"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(new, f)
    assert manifest.problems(new, root=root) == []

    env = dict(os.environ, PYTHONPATH=manifest.ROOT)
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "resnet50.b128", "--rehearse", "--trace", "1"],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    # warm and traced groups of three steps each, from the new traffic file
    groups = harness.WARM_GROUPS + harness.TRACED_GROUPS
    assert result["attempted"] == 3 * groups
    assert result["metrics"]["groups_in_window"] == {
        "value": float(groups), "unit": "count"}
    # the old cell of the same tree does not report the new metric
    assert "groups_in_window" not in {
        m["name"] for m in manifest.load_cell(
            new, "resnet50.train224", root=root).per_layer}
