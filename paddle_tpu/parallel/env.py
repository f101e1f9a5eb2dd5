"""Multi-host environment: rank/world discovery + coordination bootstrap.

Replaces the reference's launcher env protocol (PADDLE_TRAINER_ID /
PADDLE_TRAINER_ENDPOINTS, /root/reference/python/paddle/distributed/launch.py:193)
and the NCCL-id gRPC rendezvous (c_gen_nccl_id_op.cc): on TPU the
JAX distributed coordination service is the bootstrap — one
jax.distributed.initialize() call per host, then every chip on every host
appears in jax.devices() and XLA collectives ride ICI/DCN.
"""
from __future__ import annotations

import os

_initialized = False


def get_rank() -> int:
    for k in ("PADDLE_TRAINER_ID", "JAX_PROCESS_ID", "RANK"):
        if k in os.environ:
            return int(os.environ[k])
    return 0


def get_endpoints() -> list:
    """Launcher-provided trainer endpoints (single source of truth for
    PADDLE_TRAINER_ENDPOINTS parsing)."""
    eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
    return [e.strip() for e in eps.split(",") if e.strip()] if eps else []


def get_world_size() -> int:
    if "PADDLE_TRAINERS_NUM" in os.environ:
        return int(os.environ["PADDLE_TRAINERS_NUM"])
    eps = get_endpoints()
    if eps:
        return len(eps)
    if "JAX_NUM_PROCESSES" in os.environ:
        return int(os.environ["JAX_NUM_PROCESSES"])
    return 1


def init_parallel_env() -> None:
    """Initialize the JAX coordination service when launched multi-host
    (paddle launcher env convention); single-process no-op."""
    global _initialized
    if _initialized:
        return
    # liveness stamping for the launcher's hang detection / elastic
    # restart (no-op unless the launcher set PADDLE_HEARTBEAT_DIR)
    from ..distributed.heartbeat import start_heartbeat

    start_heartbeat()
    # per-rank timeline collection for the launcher's merged trace
    # (no-op unless the launcher set PADDLE_TRACE_DIR via --trace_dir)
    from ..fluid.profiler import maybe_start_trace_collection

    maybe_start_trace_collection()
    # live introspection server + metrics push exporter (no-ops unless
    # the launcher set PADDLE_DEBUGZ_PORT / PADDLE_METRICS_PUSH_URL; the
    # executor step loop arms them too, for un-launched processes)
    from ..telemetry import debugz, export

    debugz.maybe_serve()
    export.maybe_start()
    world = get_world_size()
    if world > 1:
        import jax

        # CPU multi-process needs the gloo collectives backend (the TPU
        # path rides ICI/DCN natively). Sniff the env instead of calling
        # jax.default_backend(): that would initialize backends BEFORE
        # the coordination service, which breaks multi-process startup.
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        eps = get_endpoints()
        coordinator = eps[0] if eps else None
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=world,
            process_id=get_rank(),
        )
    _initialized = True
