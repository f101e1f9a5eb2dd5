"""Op micro-benchmark harness (reference operators/benchmark/op_tester.cc:
config-driven single-op timing).

Usage:
    python tools/op_bench.py matmul --shape X=1024x1024 --shape Y=1024x1024
    python tools/op_bench.py softmax --shape X=4096x4096 --repeat 50
    python tools/op_bench.py conv2d --shape Input=8x64x56x56 \
        --shape Filter=128x64x3x3 --attr strides=1,1 --out Output

    # the fused conv+BN(+relu) mega-kernel at a ResNet stage shape
    # (NHWC; Scale/Bias/Mean/Variance are the per-channel BN operands):
    python tools/op_bench.py fused_conv_bn \
        --shape Input=8x28x28x128 --shape Filter=128x128x3x3 \
        --shape Scale=128 --shape Bias=128 --shape Mean=128 \
        --shape Variance=128 \
        --attr data_format=NHWC --attr padding_algorithm=SAME \
        --attr with_relu=1 --out Y

    # sweep mode: comma-separated shape lists expand cartesian, one
    # JSON line per combination
    python tools/op_bench.py matmul --sweep \
        --shape X=512x512,1024x1024 --shape Y=512x512,1024x1024

Builds a one-op Program, runs it through the real Executor (whole-block
XLA), and reports steady-state latency after a compile warmup. The
timed loop runs under FLAGS_benchmark (the sync fence — every
dispatch blocks until the device finishes, so per-iteration latency is
honest); --no-fence restores the async-dispatch loop. --op-profile
additionally traces a few steps under FLAGS_op_profile and reports the
op's OWN attributed device time (telemetry/cost.py). --flag sets
FLAGS_* before the run (flag-gated kernels: FLAGS_conv_dw_im2col,
FLAGS_use_fused_ln, ...).
"""
import argparse
import itertools
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np


def _parse_shape(s):
    name, dims = s.split("=")
    return name, tuple(int(d) for d in dims.lower().split("x"))


def _parse_shape_list(s):
    """'slot=AxB,CxD' -> (slot, [(A,B), (C,D)]) — the --sweep form."""
    name, dims = s.split("=")
    return name, [tuple(int(d) for d in v.lower().split("x"))
                  for v in dims.split(",") if v]


def _parse_attr(s):
    k, v = s.split("=", 1)
    try:
        vals = [float(x) if "." in x else int(x) for x in v.split(",")]
        return k, vals if len(vals) > 1 else vals[0]
    except ValueError:
        return k, v


def build_one_op_program(op_type, shapes, attrs, out_slot="Out",
                         dtype="float32"):
    """One-op Program + random feed (dtype, default float32) for every
    input slot. Returns (main_program, startup_program, feed dict)."""
    import paddle_tpu.fluid as fluid

    np_dtype = np.dtype(dtype) if dtype != "bfloat16" else None
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        block = main_p.global_block()
        rng = np.random.RandomState(0)
        feed = {}
        ins = {}
        for slot, shape in shapes.items():
            n = f"in_{slot}"
            arr = rng.rand(*shape).astype(np.float32)
            if np_dtype is not None:
                arr = arr.astype(np_dtype)
            else:
                import jax.numpy as jnp

                arr = jnp.asarray(arr, jnp.bfloat16)
            block.create_var(name=n, shape=shape, dtype=arr.dtype)
            feed[n] = arr
            ins[slot] = [n]
        block.create_var(name="out")
        block.append_op(type=op_type, inputs=ins,
                        outputs={out_slot: ["out"]}, attrs=attrs)
    return main_p, startup, feed


def run_case(op_type, shapes, attrs, out_slot="Out", repeat=100, warmup=1,
             fence=True, op_profile=False, op_profile_steps=3,
             dtype="float32"):
    """Measure one (op, shapes, attrs) case; returns the machine row.

    fence=True wraps the timed loop in FLAGS_benchmark so each run()
    blocks until the device finishes. op_profile=True re-runs a few
    steps under FLAGS_op_profile and adds `op_device_us` — the op's own
    attributed per-step device time from telemetry/cost.py (0.0 when
    the backend produced no attributable device events; callers fall
    back to latency_us)."""
    import jax

    import paddle_tpu.fluid as fluid

    main_p, startup, feed = build_one_op_program(
        op_type, shapes, attrs, out_slot, dtype=dtype)
    exe = fluid.Executor()
    exe.run(startup)
    feed = {k: jax.device_put(v) for k, v in feed.items()}
    for _ in range(max(1, warmup)):
        (o,) = exe.run(main_p, feed=feed, fetch_list=["out"])  # compile
    np.asarray(o)

    prev = fluid.flags.get_flags("FLAGS_benchmark")["FLAGS_benchmark"]
    if fence:
        fluid.flags.set_flags({"FLAGS_benchmark": True})
    try:
        t0 = time.perf_counter()
        for _ in range(repeat):
            (o,) = exe.run(main_p, feed=feed, fetch_list=["out"],
                           return_numpy=False)
        np.asarray(o)
        dt = (time.perf_counter() - t0) / max(1, repeat)
    finally:
        fluid.flags.set_flags({"FLAGS_benchmark": prev})

    row = {
        "op": op_type,
        "shapes": {k: list(v) for k, v in shapes.items()},
        "attrs": {k: v for k, v in attrs.items()},
        "latency_us": round(dt * 1e6, 2),
        "fenced": bool(fence),
        "repeat": repeat,
        "dtype": str(dtype),
        "backend": jax.default_backend(),
    }
    if op_profile:
        from paddle_tpu.telemetry import cost

        rep = cost.profile_executor_run(
            exe, main_p, feed, ["out"], steps=op_profile_steps, warmup=1)
        row["op_device_us"] = round(
            rep.device_ms_for(op_type=op_type) * 1e3, 3)
        row["op_profile_coverage"] = round(rep.coverage, 4)
    return row


def sweep_cases(shape_lists):
    """Cartesian product over per-slot shape lists (slot order as
    given): yields {slot: shape} dicts."""
    names = [n for n, _ in shape_lists]
    for combo in itertools.product(*[v for _, v in shape_lists]):
        yield dict(zip(names, combo))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("op_type")
    ap.add_argument("--shape", action="append", default=[],
                    help="slot=AxBxC (float32 random input); with "
                    "--sweep, slot=AxB,CxD lists expand cartesian")
    ap.add_argument("--attr", action="append", default=[])
    ap.add_argument("--out", default="Out", help="output slot name")
    ap.add_argument("--dtype", default="float32",
                    help="input dtype (float32/bfloat16/...)")
    ap.add_argument("--repeat", type=int, default=100)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--sweep", action="store_true",
                    help="cartesian product over comma-separated --shape "
                    "lists; one JSON line per combination")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable mode: JSON rows only on "
                    "stdout (diagnostics to stderr)")
    ap.add_argument("--no-fence", action="store_true",
                    help="async-dispatch timed loop (no FLAGS_benchmark "
                    "sync fence)")
    ap.add_argument("--op-profile", action="store_true",
                    help="also report the op's own attributed device "
                    "time per step (FLAGS_op_profile + "
                    "telemetry/cost.py)")
    ap.add_argument("--flag", action="append", default=[],
                    help="FLAGS_name=value set before the run")
    args = ap.parse_args()

    import paddle_tpu.fluid as fluid

    if args.flag:
        fluid.flags.set_flags(dict(f.split("=", 1) for f in args.flag))

    attrs = dict(_parse_attr(a) for a in args.attr)
    if args.sweep:
        shape_lists = [_parse_shape_list(s) for s in args.shape]
        cases = list(sweep_cases(shape_lists))
    else:
        cases = [dict(_parse_shape(s) for s in args.shape)]

    ok = 0
    for i, shapes in enumerate(cases):
        if args.sweep and not args.json:
            print(f"# case {i + 1}/{len(cases)}: "
                  + " ".join(f"{k}={list(v)}" for k, v in shapes.items()),
                  file=sys.stderr)
        try:
            row = run_case(
                args.op_type, shapes, attrs, out_slot=args.out,
                repeat=args.repeat, warmup=args.warmup,
                fence=not args.no_fence, op_profile=args.op_profile,
                dtype=args.dtype)
            ok += 1
        except Exception as e:  # noqa: BLE001 — a cartesian sweep may
            # produce shape combos the op rejects; report and move on
            row = {
                "op": args.op_type,
                "shapes": {k: list(v) for k, v in shapes.items()},
                "attrs": attrs, "error": str(e),
            }
        print(json.dumps(row))
    return 0 if (ok or not cases) else 1


if __name__ == "__main__":
    sys.exit(main())
