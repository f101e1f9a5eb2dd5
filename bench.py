"""Benchmark: BERT-base pretraining step (fwd+bwd+Adam) tokens/sec/chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline = achieved MFU / 0.35 (the BASELINE.json north star:
ERNIE/BERT-base pretraining at >=35% MFU; the reference publishes no
in-repo numbers — see BASELINE.md).

Measurement protocol (steady state, device-resident data):
  - bf16 AMP via the framework's own rewriter (contrib/mixed_precision),
    reference parity point decorator.py:218
  - the fixed batch is uploaded to the device ONCE; the step loop issues
    async dispatches and syncs once at the end — matching how a real
    input pipeline (device prefetch) behaves
  - rates and utilizations are device metrics: off the TPU "mfu" and
    "vs_baseline" print null, and a memory or goodput query that fails
    on the TPU fails the bench
"""
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _on_tpu():
    import jax

    return jax.default_backend() == "tpu"


@contextlib.contextmanager
def _best_effort_off_tpu():
    """Memory and goodput queries are diagnostics a CPU backend may not
    answer; on the TPU one that fails fails the bench."""
    try:
        yield
    except Exception:  # noqa: BLE001
        if _on_tpu():
            raise


def _mfu(flops_per_sec):
    """Model FLOP/s utilization against the chip's peak. The table lives
    in telemetry/cost.py so the bench rows and the measured-MFU gauge
    share one denominator (an unknown chip raises there). None off the
    TPU: there is no peak to divide by."""
    if not _on_tpu():
        return None
    from paddle_tpu.telemetry.cost import peak_flops_per_chip

    return round(flops_per_sec / peak_flops_per_chip(), 4)


def _bert_step_flops(cfg, batch, seq):
    """fwd+bwd FLOPs per step: 6*N per token for matmul params (fwd 2N,
    bwd 4N) + attention scores/context 12*L*S*H per token."""
    h, L, ff, v = cfg.hidden_size, cfg.num_hidden_layers, cfg.intermediate_size, cfg.vocab_size
    # parameter FLOP-active matmuls: qkv+out (4 h^2) + ffn (2 h ff) per layer
    n_matmul = L * (4 * h * h + 2 * h * ff) + v * h  # + lm head / embedding tie
    per_token = 6 * n_matmul + 12 * L * seq * h
    return per_token * batch * seq


def _timed_run(exe, program, data, loss, steps):
    """Shared measurement protocol: 2-step compile warmup + sync, async
    step loop, one trailing sync; BENCH_PROFILE wraps the timed loop.
    Returns (dt_seconds, final_loss)."""
    import contextlib

    import numpy as np

    import paddle_tpu.fluid as fluid

    for _ in range(2):
        (lv,) = exe.run(program, feed=data, fetch_list=[loss])
    float(np.asarray(lv).reshape(()))

    profile_path = os.environ.get("BENCH_PROFILE", "")
    ctx = (
        fluid.profiler.profiler(state="All", profile_path=profile_path)
        if profile_path
        else contextlib.nullcontext()
    )
    with ctx:
        t0 = time.perf_counter()
        for _ in range(steps):
            (lv,) = exe.run(program, feed=data, fetch_list=[loss],
                            return_numpy=False)
        lv = float(np.asarray(lv).reshape(()))  # one sync at the end
        dt = time.perf_counter() - t0
    assert np.isfinite(lv), f"loss not finite: {lv}"
    return dt, lv


def _maybe_op_profile(exe, program, data, loss, formula_flops_per_step,
                      model):
    """BENCH_OP_PROFILE=1: after the timed loop, re-run a few steps
    under FLAGS_op_profile and report the measured-MFU gauge + per-op
    attribution coverage in the bench row (telemetry/cost.py; the full
    report lands on the debugz /proftop endpoint and in the registry).
    The full CostReport is also persisted beside the BENCH_*.json rows
    as bench_artifacts/proftop_<model>_rNN.json (NN = next free round),
    so per-op cost history accumulates across rounds for regression
    diffing. Off = empty dict, the timed loop untouched."""
    if os.environ.get("BENCH_OP_PROFILE", "0") != "1":
        return {}
    from paddle_tpu.telemetry import cost

    rep = cost.profile_executor_run(
        exe, program, data, [loss],
        steps=int(os.environ.get("BENCH_OP_PROFILE_STEPS", "3")),
        formula_flops_per_step=formula_flops_per_step, model=model)
    _persist_cost_report(rep, model)
    return {
        "measured_mfu": rep.measured_mfu,
        "op_profile_coverage": round(rep.coverage, 4),
    }


def _persist_cost_report(rep, model) -> None:
    """Write the CostReport to bench_artifacts/proftop_<model>_rNN.json
    (atomic; NN picks up where the existing history leaves off —
    `diff`-able per-op cost rows across bench rounds). BENCH_ARTIFACTS
    overrides the directory; failures never fail the bench."""
    import glob
    import re

    try:
        art_dir = os.environ.get("BENCH_ARTIFACTS") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "bench_artifacts")
        os.makedirs(art_dir, exist_ok=True)
        taken = []
        for p in glob.glob(os.path.join(art_dir, f"proftop_{model}_r*.json")):
            m = re.search(r"_r(\d+)\.json$", p)
            if m:
                taken.append(int(m.group(1)))
        path = os.path.join(
            art_dir, f"proftop_{model}_r{max(taken, default=0) + 1:02d}.json")
        blob = json.dumps(rep.to_json(), indent=1)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(blob)
        os.replace(tmp, path)
        print(f"# proftop report persisted: {path}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — history is best-effort
        print(f"# proftop report persist failed: {e}", file=sys.stderr)


def _goodput_snapshot():
    """Stash the goodput ledger's per-bucket totals before the timed
    loop; None when PADDLE_GOODPUT is off (the default — rows stay
    bit-identical to before)."""
    with _best_effort_off_tpu():
        from paddle_tpu.telemetry import goodput

        led = goodput.get_ledger()
        if led is None:
            return None
        return dict(led.summary()["buckets_ms"])
    return None


def _goodput_fields(before):
    """BENCH_r17+ rows join the goodput ledger (PADDLE_GOODPUT=1): the
    per-bucket badput DELTA accrued over the timed loop plus the
    job-lifetime goodput ratio, so a perf row names the stalls and
    preemptions it absorbed instead of averaging them away silently.
    {} when the ledger is off."""
    if before is None:
        return {}
    with _best_effort_off_tpu():
        from paddle_tpu.telemetry import goodput

        led = goodput.get_ledger()
        if led is None:
            return {}
        summ = led.summary()
        after = summ["buckets_ms"]
        delta = {b: round(after.get(b, 0.0) - before.get(b, 0.0), 3)
                 for b in after
                 if after.get(b, 0.0) - before.get(b, 0.0) > 1e-9}
        return {"goodput_delta_ms": delta,
                "goodput_ratio": summ.get("goodput_ratio")}
    return {}


def _memory_fields(exe, program, data, loss, hbm_model_bytes=None):
    """BENCH_r06+ rows record memory alongside MFU (ISSUE 11):
    `peak_hbm_bytes` — XLA's buffer-assignment peak for the compiled
    step (measured bytes, the raw form of the existing peak_hbm_gb) —
    and `hbm_model_bytes` — params + optimizer state from the static
    live-range attribution (telemetry/memory.py), i.e. the resident
    floor a bigger batch cannot shrink. Off the TPU a backend that
    cannot report leaves the field out; on the TPU that is a failure."""
    out = {}
    with _best_effort_off_tpu():
        ma = exe.memory_analysis(program, feed=data, fetch_list=[loss])
        out["peak_hbm_bytes"] = int(ma["peak_bytes"])
    with _best_effort_off_tpu():
        if hbm_model_bytes is None:
            from paddle_tpu.telemetry import memory as _mem

            rep = _mem.build_memory_report(
                program, feed_shapes=data, fetch_names=[loss.name],
                publish=False)
            hbm_model_bytes = rep.static.model_bytes
        out["hbm_model_bytes"] = int(hbm_model_bytes)
    return out


def _emit_result(result: dict) -> None:
    """Print THE one JSON result line (the bench contract) and publish
    the same row through the unified telemetry layer — a gauge per
    numeric field in the process registry plus a kind="bench" JSONL
    record when PADDLE_METRICS_PATH is set — so BENCH_* numbers and
    production telemetry share one code path (ISSUE 4)."""
    print(json.dumps(result))
    from paddle_tpu import telemetry

    reg = telemetry.get_registry()
    metric = str(result.get("metric", "bench"))
    for key in ("value", "mfu", "peak_hbm_gb", "peak_hbm_bytes",
                "hbm_model_bytes", "vs_baseline"):
        v = result.get(key)
        if isinstance(v, (int, float)):
            reg.gauge(f"bench_{key}", metric=metric).set(v)
    telemetry.emit({"kind": "bench", **result})


def bench_resnet(depth=50):
    """Secondary tracked configs (BASELINE.md): ResNet images/sec/chip,
    any depth in the hapi roster (BENCH_MODEL=resnet18/34/50/101/152).
    BASELINE.md sets no ResNet target ("TBD"), so vs_baseline reports
    raw MFU rather than a ratio against an invented bar.

    BENCH_CONV_BN_FUSION=1 routes every conv->BN(->relu) triple through
    the fused_conv_bn mega-kernel (fluid/fusion_pass.py +
    ops/pallas/conv_bn.py); default 0 keeps the tracked baseline
    schedule. The fusion flag is reported in the JSON row."""
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import mixed_precision as mixed_prec
    from paddle_tpu.models.resnet import (
        ResNetConfig,
        build_resnet_train_program,
        resnet_step_flops,
    )

    cfg = getattr(ResNetConfig, f"resnet{depth}")()
    batch = int(os.environ.get("BENCH_BATCH", 128))
    size = int(os.environ.get("BENCH_IMAGE", 224))
    steps = int(os.environ.get("BENCH_STEPS", 20))
    use_amp = os.environ.get("BENCH_AMP", "1") == "1"
    use_fusion = os.environ.get("BENCH_CONV_BN_FUSION", "0") == "1"
    fluid.flags.set_flags({"FLAGS_conv_bn_fusion": use_fusion})

    main_p, startup = fluid.Program(), fluid.Program()
    m, st, feeds, loss = build_resnet_train_program(cfg, batch, size, main_p, startup)
    with fluid.program_guard(m, st):
        opt = fluid.optimizer.MomentumOptimizer(learning_rate=0.1, momentum=0.9)
        if use_amp:
            opt = mixed_prec.decorate(opt, use_bf16=True)
        opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(st)
    rng = np.random.RandomState(0)
    data = {
        "image": jax.device_put(rng.rand(batch, 3, size, size).astype(np.float32)),
        "label": jax.device_put(rng.randint(0, 1000, (batch, 1)).astype(np.int64)),
    }
    gp0 = _goodput_snapshot()
    dt, _ = _timed_run(exe, m, data, loss, steps)
    imgs_per_sec = batch * steps / dt
    formula_flops = resnet_step_flops(cfg, batch, size)
    _emit_result({
        "metric": f"resnet{depth}_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 1),
        "unit": "images/s/chip",
        "vs_baseline": None,  # BASELINE.md sets no ResNet target ("TBD")
        "mfu": _mfu(formula_flops * steps / dt),
        "batch": batch,
        "image_size": size,
        "steps": steps,
        "amp_bf16": use_amp,
        "conv_bn_fusion": use_fusion,
        **_memory_fields(exe, m, data, loss),
        **_goodput_fields(gp0),
        **_maybe_op_profile(exe, m, data, loss, formula_flops,
                            f"resnet{depth}"),
    })


def bench_transformer():
    """Transformer-base NMT WMT14 (the BASELINE.md configs-to-measure
    row; dist_transformer.py recipe) tokens/sec/chip. BASELINE.md's
    metric table sets no Transformer target, so vs_baseline is null and
    achieved utilization is reported in the separate "mfu" key."""
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import mixed_precision as mixed_prec
    from paddle_tpu.models.transformer import (
        TransformerConfig,
        build_transformer_nmt_program,
        random_nmt_batch,
        transformer_step_flops,
    )

    cfg = TransformerConfig.base()
    # at d_model 512 / s256 XLA's per-layer lowering beats the layer scan
    # (the stacked-param dynamic-slices dominate); the scan stays available
    # for deep/compile-bound configs via BENCH_FUSE=1
    cfg.fuse_stack = os.environ.get("BENCH_FUSE", "0") == "1"
    cfg.use_flash = os.environ.get("BENCH_FLASH", "1") == "1"
    # the non-fused path gates flash through the flag, not cfg
    fluid.flags.set_flags({"FLAGS_use_flash_attention": cfg.use_flash})
    batch = int(os.environ.get("BENCH_BATCH", 64))
    src_len = int(os.environ.get("BENCH_SRC", 256))
    trg_len = int(os.environ.get("BENCH_TRG", 256))
    steps = int(os.environ.get("BENCH_STEPS", 20))
    use_amp = os.environ.get("BENCH_AMP", "1") == "1"

    m, st, feeds, loss = build_transformer_nmt_program(
        cfg, batch, src_len, trg_len)
    with fluid.program_guard(m, st):
        opt = fluid.optimizer.AdamOptimizer(learning_rate=1e-4)
        if use_amp:
            opt = mixed_prec.decorate(opt, use_bf16=True)
        opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(st)
    data = {k: jax.device_put(np.asarray(v))
            for k, v in random_nmt_batch(cfg, batch, src_len, trg_len).items()}
    gp0 = _goodput_snapshot()
    dt, _ = _timed_run(exe, m, data, loss, steps)
    tokens_per_sec = batch * (src_len + trg_len) * steps / dt
    _emit_result({
        "metric": "transformer_base_nmt_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,  # BASELINE.md sets no Transformer target
        "mfu": _mfu(transformer_step_flops(cfg, batch, src_len, trg_len)
                    * steps / dt),
        "batch": batch,
        "src_len": src_len,
        "trg_len": trg_len,
        "steps": steps,
        "amp_bf16": use_amp,
        **_goodput_fields(gp0),
    })


# auto-remat escalation ladder: cheapest recompute first. The bench
# probes each candidate's XLA memory analysis (compile only, no execute)
# and runs the first whose projected peak fits HBM — no hand-picked
# BENCH_REMAT_* env vars needed for long-context configs. Measured on
# v5e s512/b64 (BSH kernel): remat_ffn 0.572 MFU @ 10.2G, policy
# 'flash' 0.545 @ 4.6G, remat_layer last resort.
_REMAT_LADDER = (
    {"remat_ffn": True},
    {"remat_policy": "flash"},
    {"remat_layer": True},
)


def _remat_from_env():
    """Explicit BENCH_REMAT_* env vars override the auto ladder."""
    out = {}
    for env, field in (
        ("BENCH_REMAT_FFN", "remat_ffn"),
        ("BENCH_REMAT_QKV", "remat_qkv"),
        ("BENCH_REMAT_LAYER", "remat_layer"),
    ):
        if env in os.environ:
            out[field] = os.environ[env] == "1"
    if os.environ.get("BENCH_REMAT_POLICY"):
        out["remat_policy"] = os.environ["BENCH_REMAT_POLICY"]
    return out or None


def _hbm_limit_bytes():
    import jax

    if _on_tpu():
        return int(jax.local_devices()[0].memory_stats()["bytes_limit"])
    return None  # the CPU backend has no HBM to budget against


def _apply_smoke_defaults():
    """`bench.py --smoke` (CI): tiny shapes, 2 steps — asserts the bench
    path still builds, trains, and emits one valid JSON line on the CPU
    backend. Explicit BENCH_* env vars still win (setdefault)."""
    for k, v in (
        ("BENCH_BATCH", "2"),
        ("BENCH_STEPS", "2"),
        ("BENCH_IMAGE", "32"),
        ("BENCH_SEQ", "64"),
        ("BENCH_SRC", "32"),
        ("BENCH_TRG", "32"),
        ("BENCH_LONG_SEQ", "0"),
    ):
        os.environ.setdefault(k, v)


def main():
    if "--smoke" in sys.argv:
        _apply_smoke_defaults()
    model = os.environ.get("BENCH_MODEL", "bert")
    if model.startswith("resnet"):
        return bench_resnet(int(model[len("resnet"):] or 50))
    if model == "transformer":
        return bench_transformer()

    batch = int(os.environ.get("BENCH_BATCH", 64))
    seq = int(os.environ.get("BENCH_SEQ", 512))
    # 76 is the tracked-config value (s512); clamp for short --smoke
    # sequences — more masked predictions than tokens cannot gather
    max_preds = min(76, seq // 2)
    steps = int(os.environ.get("BENCH_STEPS", 30))
    use_amp = os.environ.get("BENCH_AMP", "1") == "1"

    out = _run_bert(batch, seq, max_preds, steps, use_amp)
    result = {
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": out["tokens_per_sec"],
        "unit": "tokens/s/chip",
        "vs_baseline": _ratio(out["mfu"], 0.35),
        "mfu": out["mfu"],
        "batch": batch,
        "seq_len": seq,
        "steps": steps,
        "amp_bf16": use_amp,
        "remat": out["remat"],
        "peak_hbm_gb": out["peak_hbm_gb"],
    }
    for k in ("measured_mfu", "op_profile_coverage", "peak_hbm_bytes",
              "hbm_model_bytes"):
        if k in out:
            result[k] = out[k]
    # long-context guard row (VERDICT r3: the s4096 config regressed with
    # nothing measuring it): the default bench also runs s4096/b8 through
    # the auto-remat ladder and reports it in the same JSON line
    if seq == 512 and os.environ.get("BENCH_LONG_SEQ", "1") == "1":
        # full step count: the row exists to catch regressions, so
        # measure it as carefully as the main row
        ls = _run_bert(8, 4096, max_preds, steps, use_amp)
        result["long_seq"] = {
            "seq_len": 4096, "batch": 8, "mfu": ls["mfu"],
            "tokens_per_sec": ls["tokens_per_sec"], "remat": ls["remat"],
            "vs_long_target": _ratio(ls["mfu"], 0.37),
        }
    print(json.dumps(result))


def _ratio(mfu, target):
    return None if mfu is None else round(mfu / target, 4)


def _run_bert(batch, seq, max_preds, steps, use_amp):
    """Build + auto-remat-select + measure one BERT pretraining config."""
    import dataclasses

    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import mixed_precision as mixed_prec
    from paddle_tpu.models.bert import (
        BertConfig,
        build_bert_pretrain_program,
        random_pretrain_batch,
    )

    base_cfg = BertConfig.base()
    base_cfg.fuse_stack = True  # scan over layers: O(1)-in-depth compile time
    # long-context runs: the position table must cover the sequence
    base_cfg.max_position_embeddings = max(base_cfg.max_position_embeddings, seq)

    def build(remat):
        cfg = dataclasses.replace(base_cfg, **remat)
        main_p, startup = fluid.Program(), fluid.Program()
        m, st, _feeds, loss = build_bert_pretrain_program(
            cfg, batch, seq, max_preds, main_program=main_p,
            startup_program=startup,
        )
        with fluid.program_guard(m, st):
            opt = fluid.optimizer.AdamOptimizer(learning_rate=1e-4)
            if use_amp:
                opt = mixed_prec.decorate(opt, use_bf16=True)
            opt.minimize(loss)
        exe = fluid.Executor()
        exe.run(st)
        return cfg, exe, m, loss

    data = random_pretrain_batch(base_cfg, batch, seq, max_preds, seed=0)
    # device-resident feed: upload once, reuse every step
    data = {k: jax.device_put(np.asarray(v)) for k, v in data.items()}

    env_remat = _remat_from_env()
    candidates = [env_remat] if env_remat else list(_REMAT_LADDER)
    limit = _hbm_limit_bytes()
    peak_gb = None
    for i, remat in enumerate(candidates):
        cfg, exe, m, loss = build(remat)
        last = i == len(candidates) - 1
        if last and limit is None:
            break
        try:
            ma = exe.memory_analysis(m, feed=data, fetch_list=[loss])
        except Exception as e:  # XLA compile-time HBM OOM -> escalate
            if last or "memory" not in str(e).lower():
                raise
            print(f"# remat {remat} failed to compile: OOM; escalating",
                  file=sys.stderr)
            continue
        peak_gb = round(ma["peak_bytes"] / 2**30, 3)
        if last or limit is None or ma["peak_bytes"] <= limit * 0.95:
            break
        print(f"# remat {remat} projected {peak_gb} GiB > "
              f"{round(0.95 * limit / 2**30, 2)} GiB budget; escalating",
              file=sys.stderr)

    gp0 = _goodput_snapshot()
    dt, _ = _timed_run(exe, m, data, loss, steps)
    formula_flops = _bert_step_flops(cfg, batch, seq)
    remat_desc = cfg.remat_policy or ",".join(
        k for k in ("remat_ffn", "remat_qkv", "remat_layer")
        if getattr(cfg, k)
    ) or "none"
    mem_fields = _memory_fields(exe, m, data, loss)
    if peak_gb is not None and "peak_hbm_bytes" not in mem_fields:
        mem_fields["peak_hbm_bytes"] = int(peak_gb * 2**30)
    return {
        "tokens_per_sec": round(batch * seq * steps / dt, 1),
        "mfu": _mfu(formula_flops * steps / dt),
        "remat": remat_desc,
        "peak_hbm_gb": peak_gb if peak_gb is not None
        else _peak_hbm_gb(exe, m, data, loss),
        **mem_fields,
        **_goodput_fields(gp0),
        **_maybe_op_profile(exe, m, data, loss, formula_flops, "bert"),
    }


def _peak_hbm_gb(exe, program, data, loss):
    """XLA's buffer-assignment peak for the compiled step (the measured
    form of the remat-vs-batch tradeoff); None when a backend other
    than the TPU cannot report it."""
    with _best_effort_off_tpu():
        ma = exe.memory_analysis(program, feed=data, fetch_list=[loss])
        return round(ma["peak_bytes"] / 2**30, 3)
    return None


if __name__ == "__main__":
    main()
