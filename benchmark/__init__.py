"""The benchmark of this repository: `python3 benchmark/run.py --workload <cell>`.

Everything that decides a number lives under this directory, where a PR that
claims a gain cannot change it; `PERF.md` explains the pieces.
"""
