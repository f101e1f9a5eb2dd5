"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

    python3 chip_smoke.py        # on a TPU host; one process; < 20 minutes

Trains BERT-base (12 layers, hidden 768, FFN 3072, vocabulary 30,522;
batch 64, sequence 512, 76 masked positions; bf16 AMP, scan-fused stack,
remat_ffn, flash attention, fused LayerNorm, dropout on; random weights
from the program's seed) through the entry points a user calls:
build_bert_pretrain_program -> mixed_precision.decorate ->
Optimizer.minimize -> Executor.run(startup) -> Executor.run(main, feed=...)
with host numpy feeds and numpy fetches, one batch repeated.

It passes when every loss is finite and the last is below the first, the
compiled step that ran holds the flash and fused-LN Mosaic calls, the
parameters live on TPU devices and the scope's PRNG key is the rbg typed
key. On a host with four chips or more the same program first runs
data-parallel over four of them (fleet, mesh dp=4), and must shard its
feed four ways and fill the four chips evenly.

Nothing here is caught: a phase that fails raises, the exit status is
non-zero and the result line is not printed. Off the TPU it refuses to
run. The times and rates it prints are information for the reader, not
claims, and carry the device they came from.
"""
import importlib.metadata
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BATCH, SEQ, MAX_PREDS = 64, 512, 76
STEPS = 12
MESH_STEPS = 6
# pallas_call name= of the kernels a BERT step must keep (ops/pallas/)
STEP_KERNELS = ("flash_bsh_fwd", "flash_bsh_bwd", "add_ln_fwd", "add_ln_bwd")


def check(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def peak_bytes(device):
    return device.memory_stats()["peak_bytes_in_use"]


def build_step(cfg, batch, seq, max_preds, mesh_axes=None):
    """The trainer through the entry points a user calls: BERT
    pretraining program -> bf16 AMP -> (fleet over mesh_axes) -> Adam
    minimize. Returns (main, startup, loss). tests/test_tpu_lowering.py
    lowers the same construction at a small size."""
    import paddle_tpu.fleet as fleet
    import paddle_tpu.fluid as fluid
    from paddle_tpu.contrib import mixed_precision as mixed_prec
    from paddle_tpu.models.bert import build_bert_pretrain_program

    main, startup = fluid.Program(), fluid.Program()
    main, startup, _, loss = build_bert_pretrain_program(
        cfg, batch, seq, max_preds, main_program=main,
        startup_program=startup)
    with fluid.program_guard(main, startup):
        opt = mixed_prec.decorate(
            fluid.optimizer.AdamOptimizer(learning_rate=1e-4), use_bf16=True)
        if mesh_axes:
            strategy = fleet.DistributedStrategy()
            strategy.mesh_axes = mesh_axes
            fleet.init()
            opt = fleet.distributed_optimizer(opt, strategy)
        opt.minimize(loss, startup_program=startup)
    return main, startup, loss


def train_phase(label, steps, mesh_axes=None):
    """Build, start and train the configuration above; returns the facts
    the caller checks and prints."""
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.executor import Scope
    from paddle_tpu.models.bert import BertConfig, random_pretrain_batch

    cfg = BertConfig.base()
    cfg.fuse_stack = True
    cfg.remat_ffn = True
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, SEQ)
    main, startup, loss = build_step(cfg, BATCH, SEQ, MAX_PREDS, mesh_axes)

    exe, scope = fluid.Executor(), Scope()
    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    startup_s = time.perf_counter() - t0

    feed = random_pretrain_batch(cfg, BATCH, SEQ, MAX_PREDS, seed=0)
    check(all(isinstance(v, np.ndarray) for v in feed.values()),
          "feeds must be host numpy arrays")

    def step():
        (lv,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        check(isinstance(lv, np.ndarray), "fetches must be numpy arrays")
        return float(lv.reshape(()))

    t0 = time.perf_counter()
    losses = [step()]
    first_step_s = time.perf_counter() - t0
    losses.append(step())  # past any second-call retrace before timing
    t0 = time.perf_counter()
    losses += [step() for _ in range(steps - 2)]
    jax.block_until_ready(list(scope.vars.values()))
    steady_s = (time.perf_counter() - t0) / (steps - 2)

    print(f"{label}: losses " + " ".join(f"{v:.4f}" for v in losses))
    check(all(np.isfinite(losses)), f"{label}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{label}: loss did not fall on a repeated batch: {losses}")

    # nothing gave way: the very step that ran, compiled (a compile-cache
    # read), still holds every Mosaic call
    t0 = time.perf_counter()
    compiled = exe.aot_step(main, feed=feed, fetch_list=[loss], scope=scope)
    aot_s = time.perf_counter() - t0
    mosaic = [ln for ln in compiled.as_text().splitlines()
              if 'custom_call_target="tpu_custom_call"' in ln]
    missing = [k for k in STEP_KERNELS if not any(k in ln for ln in mosaic)]
    check(not missing,
          f"{label}: Mosaic calls missing from the compiled step: {missing} "
          f"(it holds {len(mosaic)} tpu_custom_call instructions)")

    for p in main.all_parameters():
        platforms = {d.platform for d in scope.find_var(p.name).devices()}
        check(platforms == {"tpu"},
              f"{label}: parameter {p.name} lives on {platforms}")
    check(jax.dtypes.issubdtype(scope._rng_key.dtype, jax.dtypes.prng_key)
          and str(jax.random.key_impl(scope._rng_key)) == "rbg",
          f"{label}: scope PRNG key is {scope._rng_key.dtype}, not key<rbg>")

    mem = compiled.memory_analysis()
    return {
        "cfg": cfg,
        "compiled": compiled,
        "mosaic_calls_in_step": len(mosaic),
        # memory_stats' peak leaves the executable's temporaries out
        "xla_buffer_peak_bytes": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        "startup_s": round(startup_s, 2),
        "first_step_s_incl_compile": round(first_step_s, 2),
        "aot_step_s": round(aot_s, 2),
        "steady_step_ms": round(steady_s * 1e3, 2),
        "tokens_per_s": round(BATCH * SEQ / steady_s, 1),
        "loss_first": losses[0],
        "loss_last": losses[-1],
    }


def four_chip_phase():
    """dp=4 over the first four chips, global batch 64."""
    import jax

    out = train_phase("four_chip dp=4", MESH_STEPS, mesh_axes={"dp": 4})
    # the compiled step's own input sharding for the feed
    feed_shardings = out.pop("compiled").input_shardings[0][0]
    sh = feed_shardings["input_ids"]
    check(len(sh.device_set) == 4
          and sh.shard_shape((BATCH, SEQ)) == (BATCH // 4, SEQ),
          f"four_chip: input_ids feed sharding is {sh}")
    peaks = [peak_bytes(d) for d in jax.devices()[:4]]
    check(max(peaks) <= 2 * min(peaks),
          f"four_chip: per-device peak bytes not within 2x: {peaks}")
    out.pop("cfg")
    out["feed_shard_shape"] = list(sh.shard_shape((BATCH, SEQ)))
    out["peak_bytes_in_use_per_device"] = peaks
    return out


def one_chip_phase(peak_flops):
    import jax

    import bench

    out = train_phase("one_chip", STEPS)
    out.pop("compiled")
    flops = bench._bert_step_flops(out.pop("cfg"), BATCH, SEQ)
    out["mfu_vs_table_peak"] = round(
        flops / (out["steady_step_ms"] / 1e3) / peak_flops, 4)
    out["peak_bytes_in_use"] = peak_bytes(jax.devices()[0])
    return out


def main():
    import jax
    import jaxlib

    from paddle_tpu.telemetry.cost import peak_flops_per_chip

    dev = jax.devices()[0]
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found backend "
                 f"{jax.default_backend()!r} ({dev.device_kind!r}). Run it "
                 f"on the chip host, one process at a time.")
    peak_flops = peak_flops_per_chip()  # raises on a kind not in the table
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({
        "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": importlib.metadata.version("libtpu")},
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "table_peak_bf16_flops": peak_flops,
    }))

    # the four-chip phase goes first: its memory check reads each
    # device's lifetime peak, which the one-chip phase raises on device 0
    if jax.device_count() >= 4:
        info4 = four_chip_phase()
        print(json.dumps({"informational_four_chip": info4, "device": device}))
    else:
        print(f"four_chip_phase: not run, jax.device_count() is "
              f"{jax.device_count()} and it needs 4")
    info1 = one_chip_phase(peak_flops)
    print(json.dumps({"informational_one_chip": info1, "device": device}))

    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
