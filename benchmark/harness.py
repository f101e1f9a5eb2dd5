"""One run of one cell: set-up, correctness check, window, result line.

The loop is the one a user of the trainer writes: a `DataLoader` over host
batches, `Executor.run(feed=..., fetch_list=[loss], return_numpy=False)`
every step, and the loss brought to the host every `log_every` steps. That
fetch is the only sync; `log_every` steps ending on it are a *group*, timed
by the host clock. Nothing is fenced, and nothing here asks which cell it
runs: sizes come from the cell's files, model code from its family file,
per-layer metrics from their reader files.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import math
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import hlo_text, manifest, trace_reduce

# how a traced run is cut: untraced groups first, so that the traced ones
# run warm and the two can be compared inside one process
WARM_GROUPS = 2
TRACED_GROUPS = 2
TRACE_DIR = os.path.join(manifest.ROOT, ".bench_trace")
PROGRAM_COUNTERS = ("executor_cache_misses_total", "executor_retraces_total",
                    "executor_cache_hits_total")


class Spans:
    """Named host-clock spans of set-up, in seconds."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = (self.seconds.get(name, 0.0)
                                  + time.perf_counter() - t)


class CompileLog:
    """Every compile request JAX makes in this process, from its own
    monitoring events: the benchmark's count, beside the program's."""

    def __init__(self):
        import jax

        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def program_counters() -> Dict[str, float]:
    from paddle_tpu.telemetry import get_registry

    reg = get_registry()
    return {n: float(reg.counter(n).value) for n in PROGRAM_COUNTERS}


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Built:
    main: Any
    startup: Any
    loss: Any
    feed_names: List[str]
    grad_of: Dict[str, str]


def build_program(cell: manifest.Cell, batch: int, dropout: bool,
                  seed: int) -> Built:
    """build_*_program -> mixed_precision.decorate(use_bf16=True) -> fleet
    where the cell has a mesh -> minimize, as a user calls them. Names
    restart for every program, so the check program and the cell's own
    name their parameters alike."""
    import paddle_tpu.fleet as fleet
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import mixed_precision

    if cell.config["program"]["amp"] != "bf16":
        raise ValueError("the harness builds bf16 AMP programs only")
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard():
        loss, feed_names = cell.family.build_forward(
            cell.config, cell.traffic, batch, dropout, main, startup)
        with fluid.program_guard(main, startup):
            opt = mixed_precision.decorate(
                cell.family.optimizer(cell.config, batch), use_bf16=True)
            if cell.mesh_axes:
                strategy = fleet.DistributedStrategy()
                strategy.mesh_axes = dict(cell.mesh_axes)
                fleet.init()
                opt = fleet.distributed_optimizer(opt, strategy)
            _, params_grads = opt.minimize(loss, startup_program=startup)
    return Built(main, startup, loss, list(feed_names),
                 {p.name: g.name for p, g in params_grads if g is not None})


def batch_rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([seed, stream, index])


# ---------------------------------------------------------------------------
# correct, outside the window
# ---------------------------------------------------------------------------


def run_check(cell: manifest.Cell, seed: int) -> dict:
    """The check program (the cell's configuration, both dropout rates 0, a
    small batch at the cell's sequence length or image size, the cell's
    mesh) against the family's plain float32 reference on the weights read
    out of the check's own scope: loss, the gradients of the family's
    named parameters, and three optimizer steps on one batch that lower
    the loss each time. Its buffers are dropped before it returns."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import Scope

    family, config, traffic = cell.family, cell.config, cell.traffic
    batch = int(traffic["check_batch"])
    built = build_program(cell, batch, dropout=False, seed=seed)
    exe, scope = fluid.Executor(), Scope()
    exe.run(built.startup, scope=scope)
    feed = family.make_batch(config, traffic, batch, batch_rng(seed, 2))

    # the reference first: the step donates the parameters' buffers
    params = {p.name: scope.find_var(p.name)
              for p in built.main.all_parameters()}
    ref_loss, ref_grads = family.reference_loss_and_grads(
        config, traffic, params, feed)
    del params

    wanted = family.check_parameters(config)
    names = sorted({p for _, p, _ in wanted})
    fetch = [built.loss.name] + [built.grad_of[n] for n in names]
    losses = []
    errors = {}
    for step in range(4):
        out = exe.run(built.main, feed=feed, fetch_list=fetch, scope=scope,
                      return_numpy=False)
        losses.append(float(np.asarray(out[0]).reshape(())))
        if step == 0:
            got = dict(zip(names, out[1:]))
            for label, name, index in wanted:
                g, r = got[name], ref_grads[name]
                if index is not None:
                    g, r = g[index], r[index]
                g = jnp.asarray(g, jnp.float32)
                errors[label] = float(
                    jnp.linalg.norm((g - r).ravel())
                    / jnp.linalg.norm(r.ravel()))
            del got
        del out
    ref_loss = float(ref_loss)
    del ref_grads
    scope.drop_kids()

    # the tolerances stand in the configuration file, with their reasons: a
    # number for a gradient that is judged, null for one that is reported
    # only
    tol = config["check"]
    loss_err = abs(losses[0] - ref_loss) / abs(ref_loss)
    falls = all(b < a for a, b in zip(losses, losses[1:]))
    judged = {k: v for k, v in tol["grad_rel_l2"].items() if v is not None}
    ok = (math.isfinite(loss_err) and loss_err <= tol["loss_rel"]
          and all(math.isfinite(errors[k]) and errors[k] <= v
                  for k, v in judged.items())
          and len(judged) >= 1 and falls)
    return {
        "ok": bool(ok), "batch": batch, "loss": losses[0],
        "reference_loss": ref_loss, "loss_rel_error": loss_err,
        "grad_rel_l2_error": errors,
        "tolerance": {k: tol[k] for k in ("loss_rel", "grad_rel_l2")},
        "losses_over_three_steps": losses, "loss_falls": falls,
    }


def structure_check(cell: manifest.Cell, compiled, step: hlo_text.StepText,
                    feed: dict, on_tpu: bool) -> dict:
    """The compiled step of the cell holds the Mosaic calls its
    configuration file lists, and over a mesh its feed is sharded as the
    mesh says. Off the TPU the kernels' gates route to their `jnp`
    compositions, so the first half is checked on the chip only."""
    present = step.kernels
    listed = list(cell.config["mosaic_calls"])
    missing = [k for k in listed if k not in present] if on_tpu else []
    out = {"mosaic_calls_listed": listed, "mosaic_calls_present": present,
           "mosaic_calls_missing": missing, "mosaic_checked": on_tpu,
           "ok": not missing}
    if cell.mesh_axes:
        dp = int(cell.mesh_axes.get("dp", 1))
        shardings = compiled.input_shardings[0][0]
        shards = {}
        for name, value in feed.items():
            shape = np.shape(value)
            shards[name] = list(shardings[name].shard_shape(shape))
            if shards[name][0] * dp != shape[0]:
                out["ok"] = False
        out["feed_shard_shapes"] = shards
    return out


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Group:
    seconds: float
    steps: int
    failed: int
    losses: List[float]
    data_wait_s: List[float]
    run_call_s: List[float]
    traced: bool


class Loop:
    def __init__(self, exe, scope, built: Built, loader, log_every: int):
        self.exe, self.scope, self.built = exe, scope, built
        self.batches = iter(loader)
        self.log_every = log_every

    def close(self):
        self.batches.close()

    def group(self, traced: bool = False) -> Group:
        """`log_every` steps and the fetch of their losses. With `traced`
        the three phases are annotated for the profiler."""
        import jax

        note = (jax.profiler.TraceAnnotation if traced
                else lambda name: contextlib.nullcontext())
        pending, waits, calls, failed = [], [], [], 0
        start = time.perf_counter()
        for _ in range(self.log_every):
            t0 = time.perf_counter()
            with note("bench.next_batch"):
                feed = next(self.batches)
            t1 = time.perf_counter()
            try:
                with note("bench.run_call"):
                    (loss,) = self.exe.run(
                        self.built.main, feed=feed,
                        fetch_list=[self.built.loss], scope=self.scope,
                        return_numpy=False)
                pending.append(loss)
            except Exception as e:  # noqa: BLE001 — counted, and shown
                failed += 1
                print(f"benchmark: step raised {type(e).__name__}: {e}",
                      file=sys.stderr)
            t2 = time.perf_counter()
            waits.append(t1 - t0)
            calls.append(t2 - t1)
        with note("bench.sync"):
            try:
                losses = [float(np.asarray(v).reshape(()))
                          for v in jax.device_get(pending)]
            except Exception as e:  # noqa: BLE001 — a failed sync fails the group
                print(f"benchmark: sync raised {type(e).__name__}: {e}",
                      file=sys.stderr)
                losses, failed = [], self.log_every
        seconds = time.perf_counter() - start
        if not all(math.isfinite(v) for v in losses):
            failed = self.log_every
        return Group(seconds, self.log_every, failed, losses, waits, calls,
                     traced)


# ---------------------------------------------------------------------------
# what the per-layer readers see
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunFacts:
    cell: manifest.Cell
    peaks: Optional[dict]
    groups: List[Group]
    setup: Dict[str, float]
    counters: Dict[str, float]  # moved inside the window
    trace: Optional[trace_reduce.TraceReduction]
    calls: Dict[str, hlo_text.MosaicCall]
    step_peak_bytes: int  # XLA's buffer assignment of the compiled step

    @property
    def chips(self) -> int:
        return self.cell.chips

    @property
    def units_per_step(self) -> int:
        """Tokens or images in one step, over all chips."""
        return self.cell.family.units_per_step(self.cell.traffic)

    def units_per_s_per_chip(self) -> Optional[float]:
        step_s = self.step_seconds()
        return (None if not step_s
                else self.units_per_step / step_s / self.chips)

    def step_seconds(self, traced: Optional[bool] = False) -> Optional[float]:
        """Median group seconds over steps a group, over the groups that
        ran without the profiler (or with it, or all for None)."""
        secs = [g.seconds / g.steps for g in self.groups
                if g.failed == 0 and traced in (None, g.traced)]
        return statistics.median(secs) if secs else None

    def per_step(self, field: str) -> Optional[float]:
        values = [v for g in self.groups for v in getattr(g, field)]
        return statistics.median(values) if values else None

    def kernel_ms_per_step(self, kernels: Sequence[str]) -> Optional[float]:
        if self.trace is None:
            return None
        if not any(k in d.kernel_ns for d in self.trace.devices
                   for k in kernels):
            return None
        return self.trace.median(
            lambda d: sum(d.kernel_ns.get(k, 0.0) for k in kernels)
        ) * 1e-6 / self.trace.steps

    def kernel_roofline_pct(self, kernels: Sequence[str]) -> Optional[float]:
        """Least time the chip could take for the calls the trace holds
        (the larger of operations over peak FLOP/s and bytes over peak
        bytes/s, from the kernel's file and the call's own shapes) over
        the time they took, in per cent."""
        if self.trace is None or self.peaks is None:
            return None
        shares = []
        for dev in self.trace.devices:
            least = took = 0.0
            for k in kernels:
                work = manifest.load_module("kernels", k)
                for instruction, ns in dev.kernel_calls.get(k, ()):
                    flops, nbytes = work.work(self.calls[instruction])
                    least += max(flops / self.peaks["bf16_flops_per_s"],
                                 nbytes / self.peaks["hbm_bytes_per_s"])
                    took += ns * 1e-9
            if took > 0:
                shares.append(100.0 * least / took)
        return statistics.median(shares) if shares else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_gate(cell: manifest.Cell, rehearse: bool):
    """No accelerator, or fewer chips than the cell asks for: no result."""
    import jax

    devices = jax.devices()
    if rehearse:
        if len(devices) < cell.chips:
            raise SystemExit(
                f"rehearsal needs {cell.chips} virtual CPU devices, JAX "
                f"shows {len(devices)}")
        return devices
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"benchmark: {cell.name} is measured on a TPU; JAX found "
            f"backend {jax.default_backend()!r} "
            f"({devices[0].device_kind!r}). --rehearse runs the cell's "
            f"rehearsal block on the CPU and reports no time.")
    if len(devices) < cell.chips:
        raise SystemExit(
            f"benchmark: {cell.name} needs {cell.chips} chips, JAX shows "
            f"{len(devices)}")
    return devices


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool, t0: float) -> int:
    spans = Spans()
    cell = manifest.load_cell(manifest.load_manifest(), workload, rehearse)

    with spans.span("import_s"):
        import jax

        devices = device_gate(cell, rehearse)
        on_tpu = jax.default_backend() == "tpu"
        peaks = (None if rehearse
                 else manifest.load_peaks(devices[0].device_kind))
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid.executor import Scope
    compile_log = CompileLog()
    traffic = cell.traffic
    batch, log_every = int(traffic["batch"]), int(traffic["log_every"])

    with spans.span("check_s"):
        check = run_check(cell, seed)

    with spans.span("program_build_s"):
        built = build_program(cell, batch, dropout=True, seed=seed)
    exe, scope = fluid.Executor(), Scope()
    with spans.span("startup_s"):
        exe.run(built.startup, scope=scope)
        jax.block_until_ready(list(scope.vars.values()))
    with spans.span("pool_s"):
        pool = [cell.family.make_batch(cell.config, traffic, batch,
                                       batch_rng(seed, 1, i))
                for i in range(int(traffic["pool"]))]

    def batches():
        for i in itertools.count():
            yield [pool[i % len(pool)][n] for n in built.feed_names]

    loader = fluid.DataLoader.from_generator(feed_list=built.feed_names)
    loader.set_batch_generator(batches)
    loop = Loop(exe, scope, built, loader, log_every=1)
    with spans.span("first_step_s"):
        warm = [loop.group()]
    with spans.span("warm_s"):
        warm.append(loop.group())
    loop.log_every = log_every
    with spans.span("aot_s"):
        compiled = exe.aot_step(built.main, feed=pool[0],
                                fetch_list=[built.loss], scope=scope)
        step_text = hlo_text.read_step(compiled.as_text())
        mem = compiled.memory_analysis()
        step_peak_bytes = (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        structure = structure_check(cell, compiled, step_text, pool[0],
                                    on_tpu)
        del compiled
    setup_s = time.perf_counter() - t0

    # ---- the window: nothing below compiles ----
    before = program_counters()
    compiles_before = compile_log.requests
    groups: List[Group] = []
    reduction = None
    if trace:
        for _ in range(WARM_GROUPS):
            groups.append(loop.group())
        reduction = traced_groups(loop, groups, step_text)
    else:
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            groups.append(loop.group())
    loop.close()
    moved = {k: v - before[k] for k, v in program_counters().items()}
    moved["jax_compile_requests"] = float(
        compile_log.requests - compiles_before)

    attempted = sum(g.steps for g in groups)
    failed = sum(g.failed for g in groups)
    no_compile = (moved["executor_cache_misses_total"] == 0
                  and moved["jax_compile_requests"] == 0)
    warm_ok = all(g.failed == 0 for g in warm)
    correct = bool(check["ok"] and structure["ok"] and no_compile
                   and warm_ok and failed == 0 and attempted > 0)

    facts = RunFacts(cell, peaks, groups,
                     {**spans.seconds, "setup_s": setup_s}, moved, reduction,
                     step_text.calls, int(step_peak_bytes))
    stats_peak = max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
         for d in devices[:cell.chips]), default=0)
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
        # the allocator's own peak leaves the executable's temporaries out
        # on this libtpu (PERF.md section 7), so the compiled step's buffer
        # assignment stands in where it is larger
        "memory_peak_bytes": (None if rehearse
                              else int(max(stats_peak, step_peak_bytes))),
    }
    if trace:
        device["busy_s"] = reduction.busy_s if reduction else None
        device["window_s"] = reduction.window_s if reduction else None

    if trace:
        metrics = read_metrics("layer_metrics", cell.per_layer, facts)
    else:
        metrics = read_metrics("end_to_end", cell.end_to_end, facts)
    if rehearse:
        # a time, rate or utilization read on the CPU is no device number
        for m in cell.end_to_end + cell.per_layer:
            if m["name"] in metrics and m["source"] != "program_counter":
                metrics[m["name"]]["value"] = None

    def ms(step_s):
        return None if rehearse or step_s is None else step_s * 1e3

    details = {
        "workload": cell.name, "seed": seed, "rehearsal": rehearse,
        "check": check, "structure": structure,
        "moved_in_window": moved,
        "setup_split_s": None if rehearse else facts.setup,
        "compile_requests": compile_log.requests,
        "compile_cache_hits": compile_log.cache_hits,
        "groups": len(groups),
        "group_seconds": None if rehearse else [g.seconds for g in groups],
        "step_peak_bytes": int(step_peak_bytes),
        "allocator_peak_bytes": int(stats_peak),
        "untraced_step_ms": ms(facts.step_seconds()),
        "traced_step_ms": ms(facts.step_seconds(traced=True)),
        "last_loss": groups[-1].losses[-1] if groups and groups[-1].losses
        else None,
    }
    print(json.dumps({"details": details}))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduction is not None and not rehearse:
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": reduction.top_gaps(10)}
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


def traced_groups(loop: Loop, groups: List[Group],
                  step_text: hlo_text.StepText,
                  ) -> Optional[trace_reduce.TraceReduction]:
    """TRACED_GROUPS groups under `jax.profiler`, inside one `bench.window`
    span, reduced before the function returns."""
    import shutil

    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the spans below are enough
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for _ in range(TRACED_GROUPS):
                groups.append(loop.group(traced=True))
    finally:
        jax.profiler.stop_trace()
    planes = trace_reduce.load_xplane(trace_reduce.find_xplane(TRACE_DIR))
    return trace_reduce.reduce_trace(
        planes, steps=TRACED_GROUPS * loop.log_every,
        kernel_of=step_text.kernel_of, label_of=step_text.label)


def read_metrics(kind: str, rows: List[dict], facts: RunFacts) -> dict:
    """Every metric of the cell through its own reader file,
    `<kind>/<name>.py`. A reader that finds nothing to read returns None
    and the metric is left out of the line."""
    out = {}
    for m in rows:
        reader = manifest.load_module(kind, m["name"])
        value = reader.read(facts)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
