"""Executor: runs a Program by JIT-compiling whole blocks via XLA.

Parity surface: reference Executor (python/paddle/fluid/executor.py:896,
paddle/fluid/framework/executor.cc:180) and Scope
(paddle/fluid/framework/scope.h:46).

TPU-native design — the central departure from the reference:
the reference interprets a block op-by-op (executor.cc:465-471), paying
per-op dispatch; here the whole block is traced once into a single JAX
function and compiled by XLA, so op boundaries vanish (fusion) and the
train step — forward, backward, optimizer update — is ONE device program.
Scope state (parameters, optimizer moments, RNG key) is threaded through
the compiled function functionally and donated, so parameters are updated
in-place in device memory. The compile cache is keyed on
(program identity+version, feed signature, fetch names), mirroring the
reference's `Executor._prepare` program cache.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import framework, monitor
from .dtypes import convert_dtype
from .profiler import RecordEvent
from ..ops import registry
from ..telemetry import numerics as _numerics
from ..telemetry import tracing as _tracing


class Scope:
    """name -> device array holder (reference scope.h:46, flat here: XLA
    owns the memory; hierarchy is unnecessary without per-op locals)."""

    def __init__(self):
        self.vars: Dict[str, Any] = {}
        self._rng_key = None

    def find_var(self, name: str):
        return self.vars.get(name)

    def var(self, name: str):
        return self.vars.setdefault(name, None)

    def set_var(self, name: str, value):
        self.vars[name] = value

    def drop_kids(self):
        self.vars.clear()


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        global _global_scope
        old = _global_scope
        _global_scope = scope
        try:
            yield
        finally:
            _global_scope = old

    return _guard()


class _CompiledBlock:
    def __init__(self, fn, feed_names, donate_names, keep_names, state_out_names, fetch_names):
        self.fn = fn
        self.feed_names = feed_names
        # scope vars read AND overwritten -> donated to XLA (in-place update)
        self.donate_names = donate_names
        # scope vars only read -> must survive the call
        self.keep_names = keep_names
        self.state_out_names = state_out_names
        self.fetch_names = fetch_names
        # name -> NamedSharding when compiled over a mesh (else empty):
        # scope arrays produced by an unsharded startup run are resharded
        # on first use (device_put is a no-op when already placed right)
        self.state_shardings: Dict[str, Any] = {}
        # the first call of `fn` traces, lowers and compiles (jax.jit is
        # lazy): until one has returned, the call is first_dispatch
        self.dispatched = False


class Executor:
    """place is accepted for API parity; JAX owns device placement."""

    def __init__(self, place=None):
        self.place = place
        self._cache: Dict[tuple, _CompiledBlock] = {}

    # ------------------------------------------------------------------
    def run(
        self,
        program: Optional[framework.Program] = None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,  # parity arg; always cached
    ):
        """One step: feed -> compiled block -> fetches.

        Every phase of the call is one `RecordEvent` (fluid/profiler.py),
        the only timer here: a `TraceAnnotation` in any profiler session,
        and the StepRecord's phase field, the profiler's list and the
        tracing ring while each is armed. `Executor::run` is the CALL (it
        used to name the dispatch alone): a `StepTraceAnnotation` numbered
        by the monitor's step, and under PADDLE_TRACING the root of the
        step's causal trace, whose trace_id every PS RPC the step issues
        from this thread shares and the kind="step" record carries
        (tracetop joins on it). Inside it, in order:

          Executor::feed      _prepare_feed                  data_wait_ms
          Executor::lookup    the cache key and the look-up; on a miss
            Executor::compile building the jit closure       compile_ms
          Executor::state     the scope's arrays, the rng key
          Executor::dispatch  the call of the compiled step: argument
                              flattening, the copy of a host feed, the
                              enqueue                        device_ms
            (Executor::first_dispatch until a call of this block has
             returned: tracing, lowering and XLA's compile   compile_ms)
          Executor::commit    rng key and new state into the scope
          Executor::fetch     np.asarray of the fetches      fetch_ms

        What is left of the call outside these is `Executor::run`'s own
        time. rec is None unless a consumer armed the monitor
        (PADDLE_METRICS_PATH, debugz, the goodput ledger): the flag-off
        hot path builds no record and no dict."""
        with RecordEvent("Executor::run", step_num=monitor.global_step()):
            rec = monitor.begin_step()
            try:
                out = self._run_impl(program, feed, fetch_list, scope,
                                     return_numpy, rec)
            except BaseException as exc:
                monitor.abandon_step()
                try:
                    # goodput ledger (ISSUE 15): an un-committed step's
                    # window is badput — BadStepError means discarded
                    # work (bad_step_replay), anything else a stall
                    from ..telemetry import goodput as _goodput

                    _goodput.on_abandoned_step(
                        type(exc).__name__ == "BadStepError")
                except Exception:  # noqa: BLE001 — accounting only
                    pass
                raise
            monitor.commit_step(rec)
        return out

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  rec):
        if program is None:
            program = framework.default_main_program()
        # CompiledProgram wrapper (compiler.py) delegates here
        if hasattr(program, "_program"):
            program = program._program
        feed = dict(feed or {})
        fetch_list = list(fetch_list or [])
        scope = scope or global_scope()

        fetch_names = tuple(
            v.name if isinstance(v, framework.Variable) else str(v) for v in fetch_list
        )
        block = program.global_block()

        with RecordEvent("Executor::feed", rec, "data_wait_ms"):
            feed_arrays = self._prepare_feed(block, feed)
        from .flags import flag

        with RecordEvent("Executor::lookup"):
            # the nan/inf debugging mode and the bad-step guard both
            # disable buffer donation (donated buffers are destroyed by
            # the step, which would make "recover / keep the last good
            # parameters" impossible), so the compile cache must
            # distinguish the modes
            check_nan = flag("FLAGS_check_nan_inf")
            check_numerics = flag("FLAGS_check_numerics")
            compiled = self._ensure_compiled(
                program, block, feed_arrays, fetch_names, scope,
                check_nan or check_numerics,
            )

        def _load(names):
            d = {}
            for n in names:
                v = scope.find_var(n)
                if v is None:
                    raise RuntimeError(
                        f"Variable {n!r} is used before initialization; "
                        f"run the startup program first."
                    )
                target = compiled.state_shardings.get(n)
                if target is not None and getattr(v, "sharding", None) != target:
                    import jax

                    v = jax.device_put(v, target)
                d[n] = v
            return d

        with RecordEvent("Executor::state"):
            self._ensure_rng(scope, program)
            donated = _load(compiled.donate_names)
            kept = _load(compiled.keep_names)
            if getattr(compiled, "repl_sharding", None) is not None:
                import jax

                if jax.process_count() > 1:
                    # multi-process jit rejects host numpy for sharded
                    # params: build global jax.Arrays from the
                    # (identical-per-process) full batch; each process
                    # materializes only its shards
                    feed_arrays = {
                        n: (
                            a if isinstance(a, jax.Array)
                            else jax.make_array_from_callback(
                                np.shape(a), compiled.feed_shardings[n],
                                lambda idx, a=a: np.asarray(a)[idx],
                            )
                        )
                        for n, a in feed_arrays.items()
                    }
                    if getattr(scope._rng_key, "sharding", None) != compiled.repl_sharding:
                        scope._rng_key = jax.device_put(
                            scope._rng_key, compiled.repl_sharding
                        )
        bench = flag("FLAGS_benchmark")
        # jax.jit compiles lazily: XLA's compile happens INSIDE the first
        # call, so that call is another span and belongs to compile_ms —
        # device_ms would otherwise spike once per signature and poison
        # step-time stats
        with (RecordEvent("Executor::dispatch", rec, "device_ms")
              if compiled.dispatched else
              monitor.CompileEvent("Executor::first_dispatch", rec,
                                   "compile_ms")):
            try:
                from ..distributed.faults import oom_point

                oom_point("run")
                fetches, new_state, new_key = compiled.fn(
                    feed_arrays, donated, kept, scope._rng_key
                )
            except Exception as e:
                from ..telemetry import memory as _memory

                if not isinstance(e, _memory.HBMOOMError) \
                        and _memory.is_oom(e):
                    # allocator OOM mid-step (jit compiles lazily, so a
                    # first-call compile OOM lands here too): the OOM
                    # doctor dumps the memory flight-record and raises
                    # with the culprit buffer + what-ifs attached
                    _memory.raise_oom(program, feed_arrays, phase="run",
                                      error=e)
                raise
            compiled.dispatched = True
            if rec is not None and bench:
                # honest device time needs a fence; gated on the same
                # FLAGS_benchmark that already syncs below, so telemetry
                # never adds a fence the run didn't opt into
                import jax

                jax.block_until_ready(fetches)
                rec.fenced = True
        if check_numerics:
            # bad-step guard (FLAGS_check_numerics): refuse to COMMIT a
            # step whose gradients went non-finite — scope (params,
            # moments, RNG key) stays exactly pre-step, so the caller
            # can skip the batch or roll back. Raised before check_nan:
            # skip semantics win over fail-fast when both are on.
            bad = self._scan_bad_step(new_state)
            if bad is not None:
                from .checkpoint import BadStepError

                # flight recorder: the spans that led to the bad step
                # are evidence — dump them BEFORE the raise unwinds
                # (no-op unless PADDLE_TRACING + PADDLE_TRACE_DIR)
                _tracing.annotate(bad_step=bad)
                _tracing.flight_dump("bad_step")
                # NaN-provenance doctor: the scope is still exactly
                # pre-step, so the failed step can be replayed eagerly
                # and bisected to its FIRST non-finite producer; the
                # numrec dump + report ride the BadStepError
                report, dump = _numerics.maybe_run_doctor(
                    program, feed_arrays, scope, reason=bad)
                detail = ""
                if report and report.get("provenance") == "op":
                    uf = report.get("user_frame")
                    detail = (
                        f"; first non-finite producer: "
                        f"op#{report['op_index']} "
                        f"[{report['op_type']}] -> "
                        f"{report['output_var']!r}"
                        + (f" at {uf[0]}:{uf[1]}" if uf else ""))
                if dump:
                    detail += f"; numerics flight-record: {dump}"
                raise BadStepError(
                    f"FLAGS_check_numerics: {bad}; step NOT committed "
                    f"(parameters, optimizer state and RNG unchanged)"
                    f"{detail}", report=report, dump_path=dump)
        if check_nan:
            # reference FLAGS_check_nan_inf scans every op output
            # (operator.cc:1020); with whole-block XLA compilation the
            # intermediates never materialize, so the per-step contract
            # here is: every fetch and every updated state var is finite.
            # Checked BEFORE committing to the scope, so a handler can
            # checkpoint/retry from the last good parameters
            self._check_nan_inf(fetch_names, fetches, new_state)
        with RecordEvent("Executor::commit"):
            scope._rng_key = new_key
            for n, v in new_state.items():
                scope.set_var(n, v)
            # numerics observability (ISSUE 12): sampled stat-var reads,
            # AMP scale transitions, SDC fingerprint publishing. Unarmed
            # cost: two attribute reads (the bit-identity contract)
            _numerics.on_step_commit(program, new_state)
        if bench:
            import jax

            jax.block_until_ready(fetches)
        if return_numpy:
            with RecordEvent("Executor::fetch", rec, "fetch_ms"):
                return [np.asarray(f) for f in fetches]
        return list(fetches)

    @staticmethod
    def _scan_bad_step(new_state):
        """Guard-var check for FLAGS_check_numerics. Programs built with
        the flag on carry one or more `check_numerics_bad_*` persistable
        vars (Optimizer._append_check_numerics_guard: an in-graph
        any-grad-non-finite reduction — grads are fused intermediates
        the host could never scan). Programs without a guard var (built
        flag-off, or no optimizer) fall back to scanning the updated
        state itself. Returns a description of the violation or None."""
        import jax.numpy as jnp

        guard_vals = {n: v for n, v in new_state.items()
                      if n.startswith("check_numerics_bad")}
        if guard_vals:
            for n, v in guard_vals.items():
                if bool(jnp.any(jnp.asarray(v) != 0)):
                    if n.startswith("check_numerics_bad_amp"):
                        return (f"AMP loss-scale backoff exhausted: "
                                f"overflow below the scale floor "
                                f"(guard {n!r})")
                    return f"non-finite gradient detected (guard {n!r})"
            return None
        for n, v in new_state.items():
            try:
                ok = bool(jnp.all(jnp.isfinite(v)))
            except TypeError:  # non-float state (ints, keys)
                continue
            if not ok:
                return f"variable {n!r} would become non-finite"
        return None

    @staticmethod
    def _check_nan_inf(fetch_names, fetches, new_state):
        import jax.numpy as jnp

        def bad(v):
            try:
                return not bool(jnp.all(jnp.isfinite(v)))
            except TypeError:  # non-float (ints, keys)
                return False

        for name, v in zip(fetch_names, fetches):
            if bad(v):
                raise FloatingPointError(
                    f"FLAGS_check_nan_inf: fetch {name!r} contains NaN/Inf"
                )
        for name, v in new_state.items():
            if bad(v):
                raise FloatingPointError(
                    f"FLAGS_check_nan_inf: variable {name!r} contains NaN/Inf "
                    f"after this step"
                )

    # ------------------------------------------------------------------
    def _ensure_compiled(self, program, block, feed_arrays, fetch_names,
                         scope, no_donate):
        """Fetch-or-build the compiled step for this cache key. Shared by
        run() and memory_analysis() so both agree on compile semantics
        (and memory_analysis can compile WITHOUT executing). no_donate:
        diagnostic/guard modes (check_nan_inf, check_numerics) must keep
        the pre-step buffers alive."""
        key = self._cache_key(program, feed_arrays, fetch_names, no_donate)
        compiled = self._cache.get(key)
        if compiled is None:
            from .flags import flag

            if flag("FLAGS_program_verify"):
                # static verification BEFORE XLA sees the block: a
                # malformed graph raises a ProgramVerifyError pointing
                # at the op's build-time call stack instead of a trace
                # error hundreds of frames deep. Flag-off cost: this one
                # dict lookup, only on a compile-cache miss.
                from .analysis import assert_valid

                assert_valid(
                    program,
                    live_out=set(feed_arrays) | set(fetch_names),
                    where="Executor compile (FLAGS_program_verify)")
                # scope-aware lint (same flag, same first-touch site):
                # every persistable the program reads before writing
                # must already be in the scope, initialized, with
                # matching shape/dtype — the finding names the var and
                # the owning layer instead of failing inside jit.
                # Orphan-scope warnings are skipped here: scopes are
                # routinely shared across programs (startup then main).
                from .analysis import assert_scope_valid

                assert_scope_valid(
                    program, scope, feed_names=set(feed_arrays),
                    check_orphans=False,
                    where="Executor compile (FLAGS_program_verify)")
            # a RETRACE is a recompile of a program the cache already
            # holds under another signature (shape change, new fetch
            # list, flag toggle) — the shape-instability tax telemetry
            # counts separately from first compiles
            retrace = any(k[0] == program._serial for k in self._cache)
            # memory observability (ISSUE 11): FLAGS_mem_profile runs
            # the static live-range pass and publishes /memz + gauges;
            # PADDLE_HBM_BUDGET_BYTES gates the static estimate BEFORE
            # paying (or failing) the XLA compile. Flag-off + env-unset
            # cost: one flag read + one env read on a cache miss.
            from ..telemetry import memory as _memory

            _memory.on_compile(program, feed_arrays, fetch_names)
            try:
                with monitor.CompileEvent(
                        "Executor::compile",
                        attrs={"retrace": retrace}) as span:
                    from ..distributed.faults import oom_point

                    oom_point("compile")
                    compiled = self._compile(
                        program, block, sorted(feed_arrays), fetch_names,
                        scope, donate=not no_donate,
                    )
            except _memory.HBMOOMError:
                raise
            except Exception as e:
                if _memory.is_oom(e):
                    # OOM doctor: XLA refused at buffer assignment —
                    # dump the memory flight-record naming the largest
                    # live buffers + what-ifs, then raise enriched
                    _memory.raise_oom(program, feed_arrays,
                                      phase="compile", error=e)
                raise
            monitor.record_compile(span.ms, retrace)
            self._cache[key] = compiled
        else:
            monitor.record_cache_hit()
        return compiled

    @staticmethod
    def _ensure_rng(scope, program):
        """Initialize the scope's PRNG key once. TPU: the rbg generator
        lowers to the hardware RNG; threefry costs real step time for
        dropout masks (profiled ~7% on BERT-base). CPU keeps threefry
        for cross-run determinism."""
        if scope._rng_key is None:
            import jax

            if jax.default_backend() == "tpu":
                # typed key: fold_in/split/bernoulli all stay rbg
                scope._rng_key = jax.random.key(
                    program.random_seed or 0, impl="rbg"
                )
            else:
                scope._rng_key = jax.random.PRNGKey(program.random_seed or 0)

    @staticmethod
    def _cache_key(program, feed_arrays, fetch_names, no_donate):
        """THE compile-cache key — run() and memory_analysis() must agree
        on its exact shape, so both build it here."""
        feed_sig = tuple(
            (n, tuple(a.shape), str(a.dtype))
            for n, a in sorted(feed_arrays.items())
        )
        from .flags import flag

        # diagnostic flags belong in the key: toggling one to debug must
        # recompile, not silently hit the pre-toggle cache entry
        # (FLAGS_op_profile changes the traced computation's metadata, so
        # toggling it back off must return to the scope-free executable)
        return (program._serial, program._version, feed_sig, fetch_names,
                no_donate, flag("FLAGS_enable_unused_var_check"),
                flag("FLAGS_program_verify"), flag("FLAGS_op_profile"),
                flag("FLAGS_tensor_stats"))

    def _prepare_feed(self, block, feed):
        import jax

        out = {}
        for name, value in feed.items():
            if isinstance(value, jax.Array):
                # device-resident feed: zero host->device traffic per step.
                # The TPU answer to the reference's double-buffered reader
                # (operators/reader/buffered_reader.cc async GPU copy):
                # callers (DataLoader, bench) device_put batches ahead of
                # the step that consumes them.
                out[name] = value
                continue
            if block.has_var(name):
                var = block.var(name)
                arr = np.asarray(value)
                if arr.dtype != var.dtype and var.dtype is not None:
                    arr = arr.astype(var.dtype)
                out[name] = arr
            else:
                out[name] = np.asarray(value)
        return out

    def _compile(self, program, block, feed_names, fetch_names, scope,
                 donate=True):
        import jax

        ops = list(block.ops)
        # classify variables: reads before writes must come from feed or scope
        written: set = set(feed_names)
        state_in: List[str] = []
        for op in ops:
            spec = registry.get(op.type)
            if spec is None:
                raise KeyError(f"op {op.type!r} has no registered emitter")
            for n in op.input_names():
                if n not in written and n not in state_in:
                    state_in.append(n)
            for n in op.output_names():
                written.add(n)

        from .flags import flag

        if flag("FLAGS_enable_unused_var_check"):
            # reference unused_var_check.cc (FLAGS_enable_unused_var_check,
            # operator.cc:987): surface feeds no op consumes — the
            # classic silently-ignored-input bug. Sub-block programs read
            # outer vars through their own ops, so only block-0 feeds
            # are checkable here; fetch-only feeds are legitimate.
            consumed = {
                n for b in program.blocks for op in b.ops
                for n in op.input_names()
            }
            unused = [n for n in feed_names
                      if n not in consumed and n not in fetch_names]
            if unused:
                import warnings

                # _compile <- _ensure_compiled <- run <- user call site
                warnings.warn(
                    f"Executor: feed variable(s) {unused} are consumed "
                    f"by no op in the program (FLAGS_enable_unused_var_"
                    f"check) — a misspelled feed name or dead input?",
                    RuntimeWarning, stacklevel=4)
        # fetches that are pure feeds/state also work
        for n in fetch_names:
            if n not in written and n not in state_in and n not in feed_names:
                state_in.append(n)

        persistable = {
            v.name
            for v in program.list_vars()
            if v.persistable
        }
        state_out = [
            n
            for n in dict.fromkeys(
                n for op in ops for n in op.output_names()
            )
            if n in persistable or scope.find_var(n) is not None
        ]

        donate_names = [n for n in state_in if n in set(state_out)]
        keep_names = [n for n in state_in if n not in set(state_out)]
        mesh = program._mesh
        # captured at compile time (the flag is in the cache key): per-op
        # named scopes for device-time attribution (telemetry/cost.py)
        op_profile = flag("FLAGS_op_profile")

        import contextlib

        def fwk_scope(name):
            # framework epilogue compute (rng advance, fetch sync) gets
            # its own named scope under FLAGS_op_profile: real device
            # time that belongs to no Program op, but must still be
            # NAMED in the cost report instead of diluting coverage
            return (jax.named_scope(f"fwk:{name}") if op_profile
                    else contextlib.nullcontext())

        # Its name is the compiled module's (`jit_step`), and that is part
        # of the key of JAX's persistent compilation cache, which op_name
        # metadata is not: under the old name (`fn`) a cache filled before
        # emit_ops wrote role scopes would go on serving executables
        # without them, identical in code and blind in a trace.
        def step(feed_vals, donated_vals, kept_vals, rng_key):
            ctx = registry.EmitContext(rng_key=rng_key, mesh=mesh,
                                       op_scopes=op_profile)
            env: Dict[str, Any] = {}
            env.update(kept_vals)
            env.update(donated_vals)
            env.update(feed_vals)
            registry.emit_ops(ctx, ops, env)
            fetches = [env[n] for n in fetch_names]
            new_state = {n: env[n] for n in state_out}
            # advance the scope key even if no op split it, so salted_rng
            # (per-op fold_in of the base key) differs across steps
            with fwk_scope("rng_advance"):
                next_key = jax.random.fold_in(ctx.rng_state, 0x5EED)
            return fetches, new_state, next_key

        manual_axes = getattr(program, "_manual_axes", None)
        if mesh is not None and manual_axes:
            # Manual multi-slice path (fleet hybrid_dcn): the whole step
            # runs inside shard_map over (dcn, dp) so per-shard gradients
            # stay VISIBLE — the program's c_dcn_grad_sync ops own the
            # two-level reduction (dense pmean over ICI, dense-or-DGC
            # over DCN) that GSPMD would otherwise fuse into one opaque
            # all-reduce. Parameters/optimizer state ride replicated;
            # identical synced grads keep them bitwise in lockstep.
            # Restriction (documented in fleet): data-parallel programs —
            # per-shard-divergent state like BN running stats is not
            # representable under the replicated out_specs.
            from jax.sharding import NamedSharding, PartitionSpec

            gblock = program.global_block()

            def pspec(name):
                v = gblock._find_var_recursive(name)
                spec = getattr(v, "_sharding", None) if v is not None else None
                if spec is None:
                    return PartitionSpec()
                return spec if isinstance(spec, PartitionSpec) else PartitionSpec(*spec)

            repl_p = PartitionSpec()
            axis_sizes = [mesh.shape[a] for a in manual_axes]

            # LocalSGD-style per-slice divergent state: stored/sharded as
            # [n_dcn, *shape] over "dcn", but ops consume the plain
            # [*shape] local view — squeeze on entry, restore on exit
            divergent = set(getattr(program, "_dcn_divergent_names", ()))

            def local_fn(feed_vals, donated_vals, kept_vals, rng_key):
                import jax.lax as lax
                import jax.numpy as jnp

                # decorrelate per-shard randomness (dropout draws differ
                # per data shard, like per-worker seeds in the reference);
                # the RETURNED key advances from the unsalted key so the
                # replicated out_spec holds
                with fwk_scope("rng_shard_salt"):
                    shard = lax.axis_index(manual_axes[0])
                    for ax, size in zip(manual_axes[1:], axis_sizes[1:]):
                        shard = shard * size + lax.axis_index(ax)
                    salted = jax.random.fold_in(rng_key, shard)
                ctx = registry.EmitContext(
                    rng_key=salted, mesh=None, manual_axes=manual_axes,
                    op_scopes=op_profile,
                )
                env: Dict[str, Any] = {}
                env.update(kept_vals)
                env.update(donated_vals)
                env.update(feed_vals)
                for n in divergent:
                    if n in env:
                        env[n] = jnp.squeeze(env[n], axis=0)
                registry.emit_ops(ctx, ops, env)
                for n in divergent:
                    if n in env:
                        env[n] = env[n][None]

                state_set = (
                    set(donate_names) | set(keep_names) | set(state_out)
                )

                def _sync(n, x):
                    # fetch contract: state vars are replicated already;
                    # scalar floats are per-shard batch metrics (mean of
                    # means == global mean); everything else is
                    # batch-sharded on dim 0 — gather it back to the
                    # global batch instead of silently averaging shards
                    if n in state_set:
                        return x
                    xa = jnp.asarray(x)
                    if xa.ndim == 0 or xa.size == 1:
                        if jnp.issubdtype(xa.dtype, jnp.floating):
                            return lax.pmean(x, manual_axes)
                        # ADVICE r3: an integer scalar is ambiguous here
                        # (per-shard count -> psum, replicated value ->
                        # identity); silently returning one shard's value
                        # was wrong either way — make the caller choose
                        raise TypeError(
                            f"manual-mesh fetch {n!r} is a non-float "
                            f"scalar: per-shard integer metrics have no "
                            f"canonical global reduction — cast it to "
                            f"float32 in-program (mean semantics) or sum "
                            f"counts in-program before fetching"
                        )
                    return lax.all_gather(x, manual_axes, axis=0, tiled=True)

                with fwk_scope("fetch_sync"):
                    fetches = [_sync(n, env[n]) for n in fetch_names]
                new_state = {n: env[n] for n in state_out}
                with fwk_scope("rng_advance"):
                    next_key = jax.random.fold_in(rng_key, 0x5EED)
                return fetches, new_state, next_key

            # state vars default to replicated; vars annotated with a
            # sharding (the DGC per-slice error-feedback buffers, sharded
            # over "dcn") keep their per-shard identity through the specs
            in_specs = (
                {n: pspec(n) for n in feed_names},
                {n: pspec(n) for n in donate_names},
                {n: pspec(n) for n in keep_names},
                repl_p,
            )
            out_specs = (
                [repl_p for _ in fetch_names],
                {n: pspec(n) for n in state_out},
                repl_p,
            )
            wrapped = jax.shard_map(
                local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )
            jit_fn = jax.jit(wrapped, donate_argnums=(1,) if donate else ())
            cb = _CompiledBlock(
                jit_fn, list(feed_names), donate_names, keep_names,
                state_out, fetch_names,
            )
            repl = NamedSharding(mesh, repl_p)
            cb.state_shardings = {
                n: NamedSharding(mesh, pspec(n))
                for n in donate_names + keep_names
            }
            cb.feed_shardings = {
                n: NamedSharding(mesh, pspec(n)) for n in feed_names
            }
            cb.repl_sharding = repl
            return cb

        if mesh is not None:
            # GSPMD path: every var maps to a NamedSharding (default
            # replicated); XLA SPMD inserts the collectives. This replaces
            # the reference's ParallelExecutor SSA-graph cloning + NCCL op
            # handles (parallel_executor.cc:470, details/all_reduce_op_handle.cc).
            from jax.sharding import NamedSharding, PartitionSpec

            gblock = program.global_block()

            def sh(name):
                v = gblock._find_var_recursive(name)
                spec = getattr(v, "_sharding", None) if v is not None else None
                return NamedSharding(mesh, spec if spec is not None else PartitionSpec())

            repl = NamedSharding(mesh, PartitionSpec())
            in_shardings = (
                {n: sh(n) for n in feed_names},
                {n: sh(n) for n in donate_names},
                {n: sh(n) for n in keep_names},
                repl,
            )
            out_shardings = (
                [sh(n) for n in fetch_names],
                {n: sh(n) for n in state_out},
                repl,
            )
            jit_fn = jax.jit(
                step,
                donate_argnums=(1,) if donate else (),
                in_shardings=in_shardings,
                out_shardings=out_shardings,
            )
            cb = _CompiledBlock(
                jit_fn, list(feed_names), donate_names, keep_names, state_out, fetch_names
            )
            cb.state_shardings = {n: sh(n) for n in donate_names + keep_names}
            cb.feed_shardings = {n: sh(n) for n in feed_names}
            cb.repl_sharding = repl
            return cb
        jit_fn = jax.jit(step, donate_argnums=(1,) if donate else ())
        return _CompiledBlock(
            jit_fn, list(feed_names), donate_names, keep_names, state_out, fetch_names
        )


    # ------------------------------------------------------------------
    def aot_step(self, program=None, feed=None, fetch_list=None,
                 scope=None):
        """AOT lower+compile the step for this (program, feed signature,
        fetch list) WITHOUT executing it, and return the jax Compiled
        object — the introspection handle behind memory_analysis()
        (.memory_analysis()), per-op cost attribution (.as_text(): the
        optimized HLO whose op_name metadata carries FLAGS_op_profile's
        op scopes — telemetry/cost.py joins xplane events through it)
        and measured flop counts (.cost_analysis()). Shares
        _ensure_compiled with run(), so the traced computation is the
        one the hot path executes; the AOT compile itself is served from
        the persistent compilation cache (paddle_tpu/__init__.py arms it)
        once the step has run — diagnostics pricing, not per-step
        pricing."""
        with monitor.CompileEvent("Executor::aot"):
            return self._lower_step(program, feed, fetch_list,
                                    scope).compile()

    def _lower_step(self, program=None, feed=None, fetch_list=None,
                    scope=None, platforms=None, sharding=None):
        """jax Lowered of the step aot_step() compiles. platforms (e.g.
        ("tpu",)) lowers for another backend than the process's own: the
        Pallas TPU lowering and its block-shape checks then run on a CPU
        host, which is what tests/test_tpu_lowering.py relies on.
        sharding (a SingleDeviceSharding on a described device) is given
        to every argument that has none, so that `.compile()` compiles
        for a chip that is not attached; the scope may then hold
        `jax.ShapeDtypeStruct`s in place of arrays, and nothing of the
        program's size is allocated."""
        traced = self._trace_step(program, feed, fetch_list, scope, sharding)
        if platforms is None:
            return traced.lower()
        return traced.lower(lowering_platforms=tuple(platforms))

    def _trace_step(self, program=None, feed=None, fetch_list=None,
                    scope=None, sharding=None):
        """jax Traced of the step `_lower_step` lowers: its `.jaxpr` holds
        every `pallas_call` the step makes, by name."""
        import jax

        if program is None:
            program = framework.default_main_program()
        if hasattr(program, "_program"):
            program = program._program
        feed = dict(feed or {})
        fetch_list = list(fetch_list or [])
        scope = scope or global_scope()
        fetch_names = tuple(
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in fetch_list
        )
        block = program.global_block()
        feed_arrays = self._prepare_feed(block, feed)
        from .flags import flag

        # compile WITHOUT executing: callers can ask "does this step fit
        # HBM?" BEFORE paying (or failing with an allocator OOM) the
        # first run — the auto-remat escalation path in bench.py. The
        # block is cached, so a subsequent run() reuses it.
        compiled = self._ensure_compiled(
            program, block, feed_arrays, fetch_names, scope,
            flag("FLAGS_check_nan_inf") or flag("FLAGS_check_numerics"),
        )
        self._ensure_rng(scope, program)
        states = {
            n: scope.find_var(n)
            for n in (compiled.donate_names + compiled.keep_names)
        }
        rng = scope._rng_key
        if any(v is None for v in states.values()):
            raise RuntimeError(
                "aot_step: run the startup program first in the "
                "SAME scope — the analysis abstracts the scope's state"
            )

        def _abstract(x, sharding=None):
            a = np.asarray(x) if not hasattr(x, "dtype") else x
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                        sharding=sharding)

        # over a mesh the state's avals carry their sharding, as the
        # arrays run() passes do: the trace — and with it the compiled
        # executable — is then the one run() already holds
        def sh(n):
            return compiled.state_shardings.get(n) or sharding

        donated = {n: _abstract(states[n], sh(n))
                   for n in compiled.donate_names}
        kept = {n: _abstract(states[n], sh(n)) for n in compiled.keep_names}
        feeds_abs = {n: _abstract(a, sharding)
                     for n, a in feed_arrays.items()}
        rng_abs = _abstract(
            rng, getattr(compiled, "repl_sharding", None) or sharding)
        return compiled.fn.trace(feeds_abs, donated, kept, rng_abs)

    def memory_analysis(self, program=None, feed=None, fetch_list=None,
                        scope=None):
        """XLA's buffer-assignment memory numbers for the compiled step
        (the measured answer to "does this batch fit?" — reference-era
        practice was trial-and-error against the allocator). Returns a
        dict with argument/output/temp/alias bytes and the derived
        `peak_bytes` (arguments + outputs + temps - aliased, XLA's HBM
        high-water estimate for one execution).

        The STARTUP program must have been run first in the given scope
        (the analysis abstracts the scope's live state); the step program
        itself is compiled on demand WITHOUT executing, so callers can
        probe "does this config fit HBM?" before the first step — the
        bench's auto-remat escalation relies on this. Cost note: the AOT
        lower().compile() does not share jax.jit's per-call executable
        cache; the two meet in the persistent compilation cache, so
        whichever comes second is a cache read. Call it for config
        probing / diagnostics, not per step.
        """
        ma = self.aot_step(program, feed, fetch_list, scope).memory_analysis()
        out = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            out[k] = int(getattr(ma, k, 0) or 0)
        out["peak_bytes"] = (
            out["argument_size_in_bytes"] + out["output_size_in_bytes"]
            + out["temp_size_in_bytes"] - out["alias_size_in_bytes"]
        )
        return out

    # ------------------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           checkpoint_dir=None, checkpoint_freq=0,
                           checkpoint_keep=3, resume=False):
        """Train by streaming batches from a Dataset (reference
        executor.py:1546 → C++ MultiTrainer/HogwildWorker hot loop,
        hogwild_worker.cc:191). The TPU executor has no per-thread scopes:
        the dataset iterator feeds the one compiled step, which is already
        the whole fwd+bwd+update program.

        checkpoint_dir arms the preemption-safe layer
        (fluid/checkpoint.py): every `checkpoint_freq` consumed batches
        the full training state (persistables, RNG, reader position) is
        committed atomically; resume=True restores the newest VALID
        checkpoint and skips the already-consumed batches, continuing
        with a bit-identical loss trace; a SIGTERM (or
        checkpoint.request_preemption()) gets a final checkpoint and
        raises checkpoint.Preempted. Under FLAGS_check_numerics a bad
        step is skipped, and after FLAGS_check_numerics_max_bad_steps
        consecutive bad steps the run rolls back to the last checkpoint
        (re-reading the dataset from its recorded position)."""
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        if thread:
            dataset.set_thread(thread)
        fetch_list = list(fetch_list or [])
        fetch_names = [
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in fetch_list
        ]
        from . import checkpoint as ckpt_mod
        from .flags import flag

        mgr = None
        consumed = 0
        if checkpoint_dir:
            if program is None:
                program = framework.default_main_program()
            if hasattr(program, "_program"):
                program = program._program
            mgr = ckpt_mod.CheckpointManager(
                checkpoint_dir, keep_last_n=checkpoint_keep,
                program=program, scope=scope or global_scope())
            ckpt_mod.install_preemption_handler()
            if resume:
                st = mgr.restore()
                if st is not None:
                    consumed = int(st["extra"].get("consumed_batches", 0))
        max_bad = max(1, int(flag("FLAGS_check_numerics_max_bad_steps")))
        bad_streak, last_rollback_sig = 0, None
        last = None
        while True:
            rolled_back = False
            step = 0
            # timed_iter: time blocked on the input iterator lands in
            # the next step record's data_wait_ms (no-op when off)
            for feed in monitor.timed_iter(dataset._as_loader(drop_last=True)):
                if step < consumed:  # replaying up to the restored position
                    step += 1
                    continue
                if mgr is not None:
                    # surface a latched async-writer failure at the
                    # step boundary (fluid/checkpoint.py error latch)
                    mgr.raise_if_async_failed()
                if mgr is not None and ckpt_mod.preemption_requested():
                    # final checkpoint is synchronous: supersede any
                    # queued async snapshot, wait out an in-flight
                    # write, commit before exiting
                    mgr.save(step, extra_state={"consumed_batches": step},
                             async_=False)
                    raise ckpt_mod.Preempted(
                        f"preemption requested: checkpointed at batch "
                        f"{step} in {checkpoint_dir!r}")
                try:
                    last = self.run(program, feed=feed,
                                    fetch_list=fetch_names, scope=scope)
                except ckpt_mod.BadStepError:
                    bad_streak += 1
                    if bad_streak >= max_bad:
                        # same-position repeat streak: the replay
                        # re-diverged deterministically — propagate
                        # instead of rolling back forever
                        sig = step - bad_streak + 1
                        if (mgr is None or mgr.latest_step() is None
                                or sig == last_rollback_sig):
                            raise
                        last_rollback_sig = sig
                        st = mgr.restore()
                        consumed = int(
                            st["extra"].get("consumed_batches", 0))
                        bad_streak = 0
                        rolled_back = True
                        break
                    step += 1  # skip the poisoned batch, keep training
                    continue
                bad_streak = 0
                if debug and fetch_names and step % print_period == 0:
                    info = fetch_info or fetch_names
                    vals = ", ".join(
                        f"{n}={np.asarray(v).reshape(-1)[0]:.6f}"
                        for n, v in zip(info, last)
                    )
                    print(f"step {step}: {vals}")
                step += 1
                if (mgr is not None and checkpoint_freq
                        and step % checkpoint_freq == 0):
                    mgr.save(step, extra_state={"consumed_batches": step})
            if not rolled_back:
                if mgr is not None:
                    # return with the checkpoints ON DISK (drain any
                    # queued/in-flight async write, surface failures)
                    mgr.drain()
                return last

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Same loop over a test-mode program (reference executor.py)."""
        return self.train_from_dataset(
            program, dataset, scope, thread, debug, fetch_list, fetch_info,
            print_period,
        )


# parity alias: reference as_lodtensor etc. are unnecessary (numpy in/out)
