"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of `BENCHMARK.json`, one result line: the last line of
standard output is a JSON object with `correct`, `attempted`, `failed`,
`metrics`, `device` and, traced, `breakdown`; the line before it holds the
details (`setup_s` split, the check's errors, group times). Without a TPU it
exits non-zero and prints no result. `--rehearse` runs the cell's `rehearsal`
blocks on the CPU (virtual devices for a four-chip cell) and prints every
time, rate and utilization as null: it is for tests and for rehearsing a
chip call, never a fallback.
"""
import time

T0 = time.perf_counter()  # set-up starts here, before JAX is imported

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    from benchmark import manifest

    doc = manifest.load_manifest()
    seconds = doc["run_seconds"] if args.seconds is None else args.seconds
    if args.rehearse:
        # before JAX is imported: the CPU, with as many virtual devices as
        # the cell has chips
        chips = next((w["chips"] for w in doc["workloads"]
                      if w["name"] == args.workload), 1)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()

    from benchmark import harness

    return harness.run_cell(args.workload, args.seed, seconds,
                            bool(args.trace), args.rehearse, T0)


if __name__ == "__main__":
    sys.exit(main())
