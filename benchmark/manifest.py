"""`BENCHMARK.json` and the files it names, found by name and by nothing else.

A cell is an entry of `workloads`. Its configuration is the file the
`configs` entry names, its traffic is `traffic/<name>.json`, its model
family is `models/<family>.py` (the configuration file's `family`), a
per-layer metric is `layer_metrics/<name>.py` and a kernel's arithmetic is
`kernels/<name>.py`. A later PR adds files and entries; nothing here is
edited for a new cell, and no code anywhere asks which cell it runs.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """`<bench_dir>/<kind>/<name>.py` as a module, or None when there is no
    such file. Loaded by path: a metric's name may hold `.` and `-`."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _overlay(base: dict, rehearse: bool) -> dict:
    """The file as it is run: with `--rehearse`, the file's `rehearsal` block
    laid over it (tiny sizes for the CPU); the block itself is dropped."""
    out = {k: v for k, v in base.items() if k != "rehearsal"}
    if rehearse:
        for k, v in base.get("rehearsal", {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = {**out[k], **v}
            else:
                out[k] = v
    return out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    family: Any
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def mesh_axes(self) -> Optional[Dict[str, int]]:
        return self.traffic.get("mesh") or None


def metrics_of(manifest: dict, kind: str, cell_name: str) -> List[dict]:
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_cell(manifest: dict, name: str, rehearse: bool = False,
              root: str = ROOT) -> Cell:
    bench_dir = os.path.join(root, manifest["paths"][0])
    rows = [w for w in manifest["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json ({known})")
    row = rows[0]
    cfg_row = next(c for c in manifest["configs"] if c["name"] == row["config"])
    config = _overlay(load_json(os.path.join(root, cfg_row["file"])), rehearse)
    traffic = _overlay(load_json(os.path.join(
        bench_dir, "traffic", row["traffic"] + ".json")), rehearse)
    family = load_module("models", config["family"], bench_dir)
    if family is None:
        raise SystemExit(f"no model family file models/{config['family']}.py")
    return Cell(
        name=name, chips=int(row["chips"]), config=config, traffic=traffic,
        family=family,
        end_to_end=metrics_of(manifest, "end_to_end", name),
        per_layer=metrics_of(manifest, "per_layer", name),
    )


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """The published peaks of one chip. A device that is not in the table is
    an error and never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"({', '.join(table['devices'])}): add its published peaks with "
            f"their source before measuring on it")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# checks a test makes, and that nothing at run time depends on
# ---------------------------------------------------------------------------


def problems(manifest: dict, root: str = ROOT) -> List[str]:
    """What is wrong with a manifest and the files it names, as sentences."""
    out: List[str] = []
    bench_dir = os.path.join(root, manifest["paths"][0])
    e2e = {m["name"] for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    configs = {c["name"]: c for c in manifest["configs"]}

    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in manifest[group]:
            if not NAME.match(row["name"]):
                out.append(f"{group}: bad name {row['name']!r}")
            if row["name"] in seen:
                out.append(f"name {row['name']!r} is used twice")
            seen.add(row["name"])
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")

    pairs = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            out.append(f"{w['name']}: no config {w['config']!r}")
            continue
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            out.append(f"{w['name']}: the pair {pair} appears twice")
        pairs.add(pair)
        if w["chips"] not in (1, 4):
            out.append(f"{w['name']}: chips must be 1 or 4")
        if len(w["why"]) > 200:
            out.append(f"{w['name']}: why is over 200 characters")
        traffic = os.path.join(bench_dir, "traffic", w["traffic"] + ".json")
        if not os.path.isfile(traffic):
            out.append(f"{w['name']}: no traffic file {traffic}")
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        out.append(f"{four} of {len(cells)} cells ask for four chips")

    used = {w["config"] for w in manifest["workloads"]}
    for name, c in configs.items():
        path = os.path.join(root, c["file"])
        if name not in used:
            out.append(f"config {name!r} is used by no cell")
        if not os.path.isfile(path):
            out.append(f"config {name!r}: no file {c['file']}")
            continue
        family = load_json(path).get("family", "")
        if not os.path.isfile(os.path.join(bench_dir, "models", family + ".py")):
            out.append(f"config {name!r}: no family file models/{family}.py")

    for m in manifest["per_layer"]:
        module = load_module("layer_metrics", m["name"], bench_dir)
        if module is None:
            out.append(f"per_layer {m['name']!r}: no reader file")
            continue
        for key in ("layer", "moves", "unit", "source"):
            if getattr(module, key.upper(), None) != m[key]:
                out.append(
                    f"per_layer {m['name']!r}: {key} is {m[key]!r} in "
                    f"BENCHMARK.json and {getattr(module, key.upper(), None)!r}"
                    f" in its file")
        if m["moves"] not in e2e:
            out.append(f"per_layer {m['name']!r} moves no end-to-end metric")
            continue
        moved = next(x for x in manifest["end_to_end"]
                     if x["name"] == m["moves"])
        for cell in m.get("workloads", cells):
            if cell not in cells:
                out.append(f"per_layer {m['name']!r}: no cell {cell!r}")
            elif cell not in moved.get("workloads", cells):
                out.append(
                    f"per_layer {m['name']!r} is reported in {cell!r}, where "
                    f"{m['moves']!r} is not")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end_to_end {m['name']!r}: source {m['source']!r}")
        if not 0 < m["bound"] <= 0.1:
            out.append(f"end_to_end {m['name']!r}: bound {m['bound']}")
    for cell in cells:
        if len(metrics_of(manifest, "end_to_end", cell)) < 2:
            out.append(f"{cell}: fewer than two end-to-end metrics")
        if not metrics_of(manifest, "per_layer", cell):
            out.append(f"{cell}: no per-layer metric")
    return out
