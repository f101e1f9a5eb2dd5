#!/usr/bin/env python
"""memtop — per-op / per-variable HBM attribution for Program IR graphs
(telemetry/memory.py; the memory-side sibling of proftop).

Builds a bench model's train graph, runs the static live-range pass
(fluid/analysis/liverange.py) and — when a backend is available — the
measured join (XLA buffer assignment + optimized-HLO op-scope
attribution), then prints buffers ranked by bytes with user callstacks,
the per-category breakdown (params / optimizer_state / gradients /
feeds / activations), attribution coverage, and the what-if levers.

`--budget <bytes>` turns memtop into a gate: exit 2 when the static
peak estimate exceeds the budget — the hook CI consumes (a program
that cannot fit HBM is rejected before it is ever run).

Examples:

    python tools/memtop.py --model resnet50
    python tools/memtop.py --model bert --json --topk 10
    python tools/memtop.py --model bert --budget 8000000000  # 8 GB gate
    python tools/memtop.py --model resnet18 --static-only
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_TOOLS_DIR))  # repo root: paddle_tpu
if _TOOLS_DIR not in sys.path:  # tools/: proglint (in-process importers)
    sys.path.insert(0, _TOOLS_DIR)

from proglint import MODELS, build_bench_model  # noqa: E402 — path above

EXIT_OVER_BUDGET = 2


def _random_feed(model, cfg, args):
    import numpy as np

    rng = np.random.RandomState(0)
    if model.startswith("resnet"):
        return {
            "image": rng.rand(args.batch, 3, args.image_size,
                              args.image_size).astype(np.float32),
            "label": rng.randint(0, cfg.num_classes,
                                 (args.batch, 1)).astype(np.int64),
        }
    from paddle_tpu.models.bert import random_pretrain_batch

    return random_pretrain_batch(cfg, args.batch, args.seq, args.max_preds,
                                 seed=0)


def build_report(args):
    """Build the model + optimizer graph and produce the MemoryReport —
    static-only (no backend required), or the full measured join."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.telemetry import memory

    main, startup, feeds, loss, cfg = build_bench_model(
        args.model, args.batch, args.image_size, args.seq, args.max_preds)
    with fluid.program_guard(main, startup):
        if args.model.startswith("resnet"):
            opt = fluid.optimizer.MomentumOptimizer(
                learning_rate=0.1, momentum=0.9)
        else:
            opt = fluid.optimizer.AdamOptimizer(learning_rate=1e-4)
        opt.minimize(loss)
    feed = _random_feed(args.model, cfg, args)
    if args.static_only:
        return memory.build_memory_report(
            main, feed_shapes=feed, fetch_names=[loss.name],
            model=args.model, budget_bytes=args.budget)
    exe = fluid.Executor()
    exe.run(startup)
    return memory.profile_executor_memory(
        exe, main, feed, [loss], model=args.model,
        budget_bytes=args.budget)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="memtop", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model", required=True,
                    help=f"bench model to build and size: "
                    f"{', '.join(MODELS)}")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--max-preds", type=int, default=8)
    ap.add_argument("--topk", type=int, default=20)
    ap.add_argument("--budget", type=int, default=None,
                    help="HBM budget in BYTES: exit "
                    f"{EXIT_OVER_BUDGET} when the static peak estimate "
                    "exceeds it (the CI feasibility gate)")
    ap.add_argument("--static-only", action="store_true",
                    help="skip the measured join (no compile, no "
                    "backend needed): live-range pass only")
    ap.add_argument("--json", action="store_true",
                    help="one JSON object (the full report) on stdout")
    args = ap.parse_args(argv)

    report = build_report(args)
    if args.json:
        print(json.dumps(report.to_json(args.topk)))
    else:
        print(report.format_table(args.topk))
    if not report.static.buffers:
        print("memtop: no sized buffers (empty program?)",
              file=sys.stderr)
        return 1
    if report.over_budget():
        print(f"memtop: static peak estimate "
              f"{report.static.peak_bytes} B exceeds --budget "
              f"{args.budget} B", file=sys.stderr)
        return EXIT_OVER_BUDGET
    return 0


if __name__ == "__main__":
    sys.exit(main())
