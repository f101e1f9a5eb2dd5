"""Crash-tolerant generation (r22): exactly-once `generate`, mid-stream
replica failover with resume, and KV-pressure preemption.

Fast lane — shares test_kv_serving.py's canonical tiny-decoder config
and pool geometry so the module reuses the jits that file already paid
for (one extra decode_step shape for the small "pressure" pool):
  * engine resume admission: bit-identical tail vs the uninterrupted
    greedy run, already-complete short-circuit (no model work), eos in
    the resumed prefix
  * cross-epoch splice refusal: typed ResumedOnNewWeights at submit
    AND at admission (weight fence lands between submit and admission)
  * preemption ladder: a fresh short request preempts the active
    request with the most remaining work, the victim resumes and
    finishes bit-identically, preempt_positions == resume_positions,
    serve_preempt/serve_resume goodput buckets accrue
  * PADDLE_SERVE_RESUME=0: r21 behavior back (resume submit refused,
    no preemption, greedy bytes unchanged)
  * temperature/top-k sampling: counter-mode determinism, resume
    replays the sampled tail, top_k=1 == argmax
  * server dedup: marked-retry generate replays/reattaches without
    running the model twice (token counters prove single execution),
    stream reattach by id, done-poll retention
  * transport drop + marked retry over real TCP: one execution
  * client failover: mid-stream replica death resumes on the promoted
    replica with the delivered prefix; full sequence == no-fault run
  * typed app errors through the client: OverloadedError,
    DeadlineExceededError, ResumedOnNewWeightsError (with the partial
    tokens attached across a failover)
  * servetop RESUME/PREEMPT columns
  * kv_cache.from_budget's page size: argument, serving env, default
  * bench.py goodput-delta row fields

Slow lane (tools/ci.sh serving drills):
  * chaos drill — two real server processes, one armed with
    `stall:gen_decode_step` + `crash:gen_decode_step`: multiple
    in-flight generations survive a mid-decode replica kill with zero
    lost requests and tokens bit-identical to the no-fault baseline
  * KV-pressure drill — pool exhaustion preempts and resumes victims
    instead of deadline-expiring them; books reconcile exactly and
    PADDLE_SERVE_RESUME=0 reproduces the r21 token stream
"""
from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.distributed import faults  # noqa: E402
from paddle_tpu.fluid import flags as fl  # noqa: E402
from paddle_tpu.fluid import layers  # noqa: E402
from paddle_tpu.inference import decode_model as dm  # noqa: E402
from paddle_tpu.inference import kv_cache as kvmod  # noqa: E402
from paddle_tpu.inference.client import (  # noqa: E402
    DeadlineExceededError, InferenceClient, OverloadedError,
    ResumedOnNewWeightsError, _map_app_error)
from paddle_tpu.inference.engine import (GenerationEngine,  # noqa: E402
                                         _sample_token)
from paddle_tpu.inference.kv_cache import PagedKVPool  # noqa: E402
from paddle_tpu.inference.server import (InferenceServer,  # noqa: E402
                                         ResumedOnNewWeights)
from paddle_tpu.telemetry import get_registry  # noqa: E402

_REG = get_registry()

# same canonical geometry as test_kv_serving.py: the module-level jits
# (prefill/decode/recompute) are shared across both files
CFG = dm.DecoderConfig()          # vocab 64, d 32, L2 H2, max_seq 64
PAGES, PSZ, SLOTS = 24, 4, 2
PROMPT = [3, 9, 1, 4, 1, 5, 9]
# the pressure pool: capacity 8 pages — one 32-position request fills
# it exactly, so a second admission MUST climb the preemption ladder
PRESSURE_PAGES = 9


def _mk_engine(kv=True, seed=1, **kw):
    kw.setdefault("n_pages", PAGES)
    kw.setdefault("page_size", PSZ)
    kw.setdefault("max_slots", SLOTS)
    if not kv:
        kw.pop("n_pages"), kw.pop("page_size")
    return GenerationEngine(dm.TinyDecoderLM(CFG, seed=seed),
                            kv_cache=kv, **kw)


def _slow_decode(monkeypatch, delay_s=0.01):
    real_step = dm.decode_step

    def slow_step(*a, **kw):
        time.sleep(delay_s)
        return real_step(*a, **kw)

    monkeypatch.setattr(dm, "decode_step", slow_step)


def _wait_admitted(eng, n_active=1, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = eng.stats()
        if st["active_slots"] >= n_active and st["queue_depth"] == 0:
            return True
        time.sleep(0.002)
    return False


def _start_tcp(handler_obj):
    from paddle_tpu.distributed.ps_server import _Handler, _TCPServer

    srv = _TCPServer(("127.0.0.1", 0), _Handler)
    srv.ps = handler_obj
    threading.Thread(target=srv.serve_forever,
                     kwargs={"poll_interval": 0.05}, daemon=True).start()
    return srv, f"127.0.0.1:{srv.server_address[1]}"


def _stop_tcp(srv):
    srv.shutdown()
    srv.close_all_connections()
    srv.server_close()


@pytest.fixture(scope="module")
def gen_frozen():
    """Tiny frozen fc model for the server's infer path (the generate
    verbs only need SOME frozen model attached)."""
    from paddle_tpu import inference

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        pred = layers.fc(x, 2)
    exe = fluid.Executor()
    scope = fluid.executor.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
    return inference.freeze_program(main, scope=scope, feed_names=["x"],
                                    fetch_list=[pred])


@pytest.fixture
def inject(monkeypatch):
    def _arm(spec: str):
        monkeypatch.setenv(faults.ENV_SPEC, spec)
        fl.set_flags({"FLAGS_ps_fault_injection": True})
        faults.reset()

    yield _arm
    fl.set_flags({"FLAGS_ps_fault_injection": False})
    faults.reset()


# ---------------------------------------------------------------------------
# engine resume admission
# ---------------------------------------------------------------------------


def test_engine_resume_tail_is_bit_identical():
    """Resuming with a prefix another run already delivered must decode
    the EXACT tail the uninterrupted run produced (greedy decode is
    deterministic within one weight epoch) — and report the splice."""
    eng = _mk_engine(kv=True)
    try:
        full = eng.result(eng.submit(PROMPT, max_new_tokens=10),
                          timeout=120)
        assert len(full["tokens"]) == 10 and full["resumed_from"] == 0
        cut = full["tokens"][:4]
        res = eng.result(eng.submit(PROMPT, max_new_tokens=10,
                                    resume_tokens=cut), timeout=120)
        assert res["tokens"] == full["tokens"]
        assert res["resumed_from"] == 4
        assert eng.counters["resumed"] == 1
        # the resume prefilled prompt+4 positions (minus prefix-cache
        # hits), never re-emitted the delivered tokens as new output
        assert eng.counters["resume_positions"] == len(PROMPT) + 4
    finally:
        eng.stop()


def test_engine_resume_already_complete_short_circuits():
    """A resume whose prefix already satisfies max_new_tokens (or ends
    at eos) lost only the done marker: finish WITHOUT touching the
    model — zero new token work."""
    eng = _mk_engine(kv=True)
    try:
        base = eng.result(eng.submit(PROMPT, max_new_tokens=4),
                          timeout=120)
        out0 = eng.counters["tokens_out"]
        done = eng.result(eng.submit(PROMPT, max_new_tokens=4,
                                     resume_tokens=base["tokens"]),
                          timeout=120)
        assert done["tokens"] == base["tokens"]
        assert done["resumed_from"] == 4
        assert eng.counters["tokens_out"] == out0  # no model execution
        # eos at the end of the delivered prefix: same short-circuit
        eos = eng.result(eng.submit(PROMPT, max_new_tokens=8, eos_id=7,
                                    resume_tokens=[5, 7]), timeout=120)
        assert eos["tokens"] == [5, 7]
        assert eng.counters["tokens_out"] == out0
    finally:
        eng.stop()


def test_engine_cross_epoch_resume_refused_at_submit():
    eng = _mk_engine(kv=True)
    try:
        with pytest.raises(ResumedOnNewWeights) as ei:
            eng.submit(PROMPT, max_new_tokens=4, resume_tokens=[1, 2],
                       expect_epoch=3)
        assert "ResumedOnNewWeights" in str(ei.value)
        assert "epoch 3" in str(ei.value)
    finally:
        eng.stop()


def test_engine_cross_epoch_resume_refused_at_admission(monkeypatch):
    """The race the submit-time check cannot see: a weight fence lands
    between submit and admission. The admission-time re-check (in the
    loop thread, where the epoch is stable) refuses the splice."""
    _slow_decode(monkeypatch, 0.005)
    eng = _mk_engine(kv=True)
    try:
        # occupy both slots so the resume has to wait in the queue
        blockers = [eng.submit(PROMPT, max_new_tokens=30)
                    for _ in range(SLOTS)]
        assert _wait_admitted(eng, n_active=SLOTS)
        res = eng.submit(PROMPT, max_new_tokens=10, resume_tokens=[1, 2],
                         expect_epoch=0)  # passes: epoch IS 0 right now
        new = {"head": np.asarray(eng.model.params["head"]) * 0.5}
        eng.stage_weights(new, version=1)
        deadline = time.monotonic() + 10
        while eng.weight_epoch == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.weight_epoch == 1
        with pytest.raises(ResumedOnNewWeights):
            eng.result(res, timeout=120)
        for b in blockers:  # the fence never hurt the live requests
            assert len(eng.result(b, timeout=120)["tokens"]) == 30
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# preemption ladder
# ---------------------------------------------------------------------------


def test_engine_preemption_ladder_resumes_victim(tmp_path, monkeypatch):
    """KV pressure: a short fresh request preempts the long-running
    victim (most remaining work), the victim's pages return, and the
    victim resumes to a bit-identical completion. Every position freed
    at preemption is matched by a position restored at resume, and the
    off-device wall time latches into serve_preempt/serve_resume."""
    from paddle_tpu.telemetry import goodput

    monkeypatch.setenv(goodput.ENV_GATE, "1")
    monkeypatch.setenv(goodput.ENV_DIR, str(tmp_path))
    goodput.reset_for_tests()
    _slow_decode(monkeypatch, 0.008)
    eng = _mk_engine(kv=True, n_pages=PRESSURE_PAGES, queue_depth=8)
    try:
        # baseline: the victim's uninterrupted greedy run
        base = eng.result(eng.submit(PROMPT, max_new_tokens=25),
                          timeout=120)["tokens"]
        assert len(base) == 25
        victim = eng.submit(PROMPT, max_new_tokens=25)
        assert _wait_admitted(eng)  # victim holds the whole pool
        short = eng.submit([11, 22, 33], max_new_tokens=4)
        s = eng.result(short, timeout=120)
        assert len(s["tokens"]) == 4  # the short was NOT starved
        v = eng.result(victim, timeout=120)
        assert v["tokens"] == base  # preempt+resume changed nothing
        c = eng.counters
        assert c["preempted"] >= 1 and c["resumed"] >= 1
        assert c["preempted"] == c["resumed"]
        assert c["preempt_positions"] == c["resume_positions"] > 0
        assert victim.preempts >= 1
        assert _REG.counter("serve_gen_preempted_total").value >= 1
        assert _REG.counter("serve_gen_resumed_total").value >= 1
        st = eng.stats()
        assert st["preempted_total"] == st["resumed_total"] >= 1
        assert st["resume_enabled"] and st["resume_queue_depth"] == 0
        buckets = goodput.get_ledger().summary()["buckets_ms"]
        assert buckets.get("serve_preempt", 0.0) > 0.0
        assert buckets.get("serve_resume", 0.0) > 0.0
    finally:
        eng.stop()
        goodput.reset_for_tests()


def test_engine_resume_flag_off_restores_r21(monkeypatch):
    """PADDLE_SERVE_RESUME=0: resume admission refused with a plain
    ValueError, no preemption ever happens, and the greedy stream is
    byte-identical to the flag-on engine's."""
    on = _mk_engine(kv=True)
    try:
        want = on.result(on.submit(PROMPT, max_new_tokens=8),
                         timeout=120)["tokens"]
    finally:
        on.stop()
    monkeypatch.setenv("PADDLE_SERVE_RESUME", "0")
    eng = _mk_engine(kv=True)
    try:
        assert eng.stats()["resume_enabled"] is False
        got = eng.result(eng.submit(PROMPT, max_new_tokens=8),
                         timeout=120)["tokens"]
        assert got == want
        with pytest.raises(ValueError) as ei:
            eng.submit(PROMPT, max_new_tokens=8, resume_tokens=[1])
        assert "PADDLE_SERVE_RESUME" in str(ei.value)
        assert eng.counters["preempted"] == 0
        assert eng.counters["resumed"] == 0
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def test_sample_token_counter_mode_unit():
    rng = np.random.default_rng(3)
    logits = rng.standard_normal(64).astype(np.float32)
    a = _sample_token(logits, 0.8, None, seed=42, index=5)
    b = _sample_token(logits, 0.8, None, seed=42, index=5)
    assert a == b  # pure function of (logits, seed, index)
    # top_k=1 collapses to argmax regardless of temperature
    assert _sample_token(logits, 5.0, 1, seed=0, index=0) \
        == int(np.argmax(logits))
    # the index is part of the counter key: different draw positions
    # decorrelate even with identical logits
    draws = {_sample_token(logits, 2.0, None, seed=42, index=i)
             for i in range(16)}
    assert len(draws) > 1
    # and different seeds give (overwhelmingly likely) different streams
    s1 = [_sample_token(logits, 2.0, None, seed=1, index=i)
          for i in range(16)]
    s2 = [_sample_token(logits, 2.0, None, seed=2, index=i)
          for i in range(16)]
    assert s1 != s2


def test_engine_sampling_deterministic_and_resume_replays():
    eng = _mk_engine(kv=True)
    try:
        kw = dict(max_new_tokens=6, temperature=0.9, seed=42)
        a = eng.result(eng.submit(PROMPT, **kw), timeout=120)["tokens"]
        b = eng.result(eng.submit(PROMPT, **kw), timeout=120)["tokens"]
        assert a == b and len(a) == 6  # same seed -> same stream
        c = eng.result(eng.submit(PROMPT, max_new_tokens=6,
                                  temperature=0.9, seed=43),
                       timeout=120)["tokens"]
        assert c != a  # the seed is live
        # counter-mode resume: token i depends on (seed, i) only, so a
        # resumed sampled generation replays the uninterrupted tail
        r = eng.result(eng.submit(PROMPT, resume_tokens=a[:3], **kw),
                       timeout=120)
        assert r["tokens"] == a and r["resumed_from"] == 3
        # greedy requests never consult the sampler (r21 bit-identity):
        # top_k=1 at any temperature reproduces the argmax stream
        g = eng.result(eng.submit(PROMPT, max_new_tokens=6),
                       timeout=120)["tokens"]
        g1 = eng.result(eng.submit(PROMPT, max_new_tokens=6,
                                   temperature=1.7, top_k=1, seed=9),
                        timeout=120)["tokens"]
        assert g1 == g
    finally:
        eng.stop()


def test_sample_token_top_p_unit():
    rng = np.random.default_rng(7)
    logits = rng.standard_normal(64).astype(np.float32)
    # top_p absent / >= 1.0 leaves the distribution untouched: the
    # r22 wire (no top_p anywhere) stays bit-identical
    for i in range(8):
        base = _sample_token(logits, 1.3, None, seed=11, index=i)
        assert _sample_token(logits, 1.3, None, seed=11, index=i,
                             top_p=None) == base
        assert _sample_token(logits, 1.3, None, seed=11, index=i,
                             top_p=1.0) == base
    # a dominant token (mass ~0.98 at temperature 1) is the whole
    # nucleus at top_p=0.5: every draw collapses onto it
    peaked = np.full(32, -4.0, np.float32)
    peaked[17] = 4.0
    for i in range(16):
        assert _sample_token(peaked, 1.0, None, seed=3, index=i,
                             top_p=0.5) == 17
    # draws never leave the nucleus (the smallest prefix of the sorted
    # distribution whose mass reaches top_p)
    temp, top_p = 1.5, 0.6
    probs = np.exp(logits.astype(np.float64) / temp
                   - (logits.astype(np.float64) / temp).max())
    probs /= probs.sum()
    order = np.argsort(-probs, kind="stable")
    cut = int(np.searchsorted(np.cumsum(probs[order]), top_p)) + 1
    nucleus = set(int(t) for t in order[:cut])
    assert 1 <= len(nucleus) < logits.size
    for i in range(64):
        tok = _sample_token(logits, temp, None, seed=5, index=i,
                            top_p=top_p)
        assert tok in nucleus
    # counter-mode contract holds with the filter on: pure function of
    # (logits, knobs, seed, index)
    assert _sample_token(logits, temp, None, seed=5, index=9,
                         top_p=top_p) \
        == _sample_token(logits, temp, None, seed=5, index=9,
                         top_p=top_p)
    # composes after top-k: with top_k=2 the nucleus is a subset of the
    # two highest-logit tokens
    top2 = set(int(t) for t in np.argsort(-logits)[:2])
    for i in range(32):
        assert _sample_token(logits, 2.0, 2, seed=8, index=i,
                             top_p=0.9) in top2


def test_engine_top_p_resume_replays_bit_identical():
    eng = _mk_engine(kv=True)
    try:
        kw = dict(max_new_tokens=6, temperature=1.2, top_p=0.8, seed=42)
        a = eng.result(eng.submit(PROMPT, **kw), timeout=120)["tokens"]
        b = eng.result(eng.submit(PROMPT, **kw), timeout=120)["tokens"]
        assert a == b and len(a) == 6
        # a mid-stream resume replays the nucleus-sampled tail exactly:
        # token i depends on (prefix logits, seed, i) only
        r = eng.result(eng.submit(PROMPT, resume_tokens=a[:2], **kw),
                       timeout=120)
        assert r["tokens"] == a and r["resumed_from"] == 2
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# server dedup: exactly-once generate
# ---------------------------------------------------------------------------


def test_server_dedup_replays_finished_reply(gen_frozen, monkeypatch):
    from paddle_tpu.inference import weight_sync as ws

    monkeypatch.setenv(ws.ENV_SYNC, "0")
    eng = _mk_engine(kv=True)
    inf = InferenceServer(gen_frozen, weight_subscribe=False, engine=eng)
    try:
        hits0 = _REG.counter("serve_gen_dedup_hits_total").value
        r1 = inf.generate(PROMPT, max_new_tokens=5, request_id="rid-1")
        out0 = eng.counters["tokens_out"]
        # marked retry after an ambiguous failure: replay, don't re-run
        r2 = inf.generate(PROMPT, max_new_tokens=5, request_id="rid-1",
                          retry=True)
        assert r2["tokens"] == r1["tokens"]
        assert eng.counters["tokens_out"] == out0  # single execution
        assert _REG.counter("serve_gen_dedup_hits_total").value \
            == hits0 + 1
        # an UNMARKED repeat of the same id is a fresh request (the
        # dedup contract rides the transport's retry marker, exactly
        # like the PS (trainer_id, step) pattern)
        inf.generate(PROMPT, max_new_tokens=5, request_id="rid-1")
        assert eng.counters["tokens_out"] == out0 + 5
    finally:
        inf.close()


def test_server_dedup_reattaches_stream_and_retains_done_polls(
        gen_frozen, monkeypatch):
    from paddle_tpu.inference import weight_sync as ws

    monkeypatch.setenv(ws.ENV_SYNC, "0")
    eng = _mk_engine(kv=True)
    inf = InferenceServer(gen_frozen, weight_subscribe=False, engine=eng)
    try:
        sid = inf.generate(PROMPT, max_new_tokens=4, stream=True,
                           request_id="rid-s")["stream_id"]
        # retried stream open reattaches to the SAME stream
        assert inf.generate(PROMPT, max_new_tokens=4, stream=True,
                            request_id="rid-s",
                            retry=True)["stream_id"] == sid
        toks, cursor = [], 0
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            snap = inf.generate_poll(stream_id=sid, cursor=cursor)
            toks += snap["tokens"]
            cursor = snap["cursor"]
            if snap["done"]:
                break
            time.sleep(0.005)
        assert len(toks) == 4
        # a RETRIED done-poll (the ack was lost) replays the final
        # snapshot from the bounded retention table instead of raising
        # "unknown stream"
        again = inf.generate_poll(stream_id=sid, cursor=0)
        assert again["done"] and again["tokens"] == toks
    finally:
        inf.close()


def test_tcp_marked_retry_runs_model_once(gen_frozen, monkeypatch,
                                          inject):
    """The transport drops the connection AFTER the generate request is
    sent (the ambiguous failure: the server is already decoding). The
    _Conn retry carries the retry marker, the server dedups on the
    request id, and the token counters prove the model ran ONCE."""
    from paddle_tpu.inference import weight_sync as ws

    monkeypatch.setenv(ws.ENV_SYNC, "0")
    eng = _mk_engine(kv=True)
    inf = InferenceServer(gen_frozen, weight_subscribe=False, engine=eng)
    srv, ep = _start_tcp(inf)
    inject("drop:generate:1")
    try:
        hits0 = _REG.counter("serve_gen_dedup_hits_total").value
        retries0 = _REG.counter("serve_retry_received_total",
                                verb="generate").value
        cli = InferenceClient([ep])
        res = cli.generate(PROMPT, max_new_tokens=5)
        assert len(res.tokens) == 5
        assert eng.counters["tokens_out"] == 5  # exactly one execution
        assert _REG.counter("serve_gen_dedup_hits_total").value \
            == hits0 + 1
        assert _REG.counter("serve_retry_received_total",
                            verb="generate").value == retries0 + 1
        cli.close()
    finally:
        _stop_tcp(srv)
        inf.close()


def test_client_plumbs_top_p_end_to_end(gen_frozen, monkeypatch):
    """top_p rides beside temperature/top-k through the whole stack:
    client kwargs -> server generate verb -> engine submit. The client
    and a direct engine submit with the same knobs produce the same
    nucleus-sampled stream, on both the blocking and streaming paths."""
    from paddle_tpu.inference import weight_sync as ws

    monkeypatch.setenv(ws.ENV_SYNC, "0")
    eng = _mk_engine(kv=True)
    inf = InferenceServer(gen_frozen, weight_subscribe=False, engine=eng)
    srv, ep = _start_tcp(inf)
    try:
        want = eng.result(
            eng.submit(PROMPT, max_new_tokens=6, temperature=1.2,
                       top_p=0.8, seed=42), timeout=120)["tokens"]
        cli = InferenceClient([ep])
        res = cli.generate(PROMPT, max_new_tokens=6, temperature=1.2,
                           top_p=0.8, seed=42)
        assert res.tokens == want
        got = []
        for chunk in cli.generate_stream(PROMPT, max_new_tokens=6,
                                         temperature=1.2, top_p=0.8,
                                         seed=42):
            got += chunk
        assert got == want
        cli.close()
    finally:
        _stop_tcp(srv)
        inf.close()


# ---------------------------------------------------------------------------
# client failover + typed errors
# ---------------------------------------------------------------------------


def test_client_stream_resumes_after_replica_death(gen_frozen,
                                                   monkeypatch):
    """Mid-stream replica death: the client promotes the live replica
    and RESUMES — delivered tokens become the new prefill prefix, and
    the full stream matches the no-fault run bit for bit."""
    from paddle_tpu.inference import weight_sync as ws

    monkeypatch.setenv(ws.ENV_SYNC, "0")
    eng_a = _mk_engine(kv=True, seed=1)
    eng_b = _mk_engine(kv=True, seed=1)  # same weights: one "epoch"
    inf_a = InferenceServer(gen_frozen, weight_subscribe=False,
                            engine=eng_a)
    inf_b = InferenceServer(gen_frozen, weight_subscribe=False,
                            engine=eng_b)
    srv_a, ep_a = _start_tcp(inf_a)
    srv_b, ep_b = _start_tcp(inf_b)
    a_stopped = False
    try:
        base_cli = InferenceClient([ep_b])
        base = base_cli.generate(PROMPT, max_new_tokens=12).tokens
        base_cli.close()
        assert len(base) == 12

        _slow_decode(monkeypatch, 0.02)
        resumes0 = _REG.counter("serve_client_stream_resumes_total").value
        # short retry deadline: the dead endpoint is detected in ~2s
        # instead of _Conn's default 10s retry budget
        cli = InferenceClient([ep_a, ep_b], deadline_secs=2.0)
        stream = cli.generate_stream(PROMPT, max_new_tokens=12,
                                     poll_s=0.005)
        got = list(next(stream))  # at least one token delivered from A
        assert got
        _stop_tcp(srv_a)  # the primary dies mid-stream
        a_stopped = True
        for chunk in stream:
            got += chunk
        assert got == base  # zero lost tokens, bit-identical splice
        assert _REG.counter("serve_client_stream_resumes_total").value \
            == resumes0 + 1
        assert eng_b.counters["resumed"] == 1
        cli.close()
    finally:
        if not a_stopped:
            _stop_tcp(srv_a)
        _stop_tcp(srv_b)
        inf_a.close()
        inf_b.close()


def test_client_cross_epoch_failover_is_typed_with_tokens(gen_frozen,
                                                          monkeypatch):
    """Failover onto a replica serving a NEWER weight epoch: splicing
    would hand the caller a sequence no single model produced, so the
    resume is refused — typed, with the partial output attached."""
    from paddle_tpu.inference import weight_sync as ws

    monkeypatch.setenv(ws.ENV_SYNC, "0")
    eng_a = _mk_engine(kv=True, seed=1)
    eng_b = _mk_engine(kv=True, seed=1)
    eng_b.stage_weights(
        {"head": np.asarray(eng_b.model.params["head"]) * 0.5},
        version=1)
    deadline = time.monotonic() + 10
    while eng_b.weight_epoch == 0 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert eng_b.weight_epoch == 1
    inf_a = InferenceServer(gen_frozen, weight_subscribe=False,
                            engine=eng_a)
    inf_b = InferenceServer(gen_frozen, weight_subscribe=False,
                            engine=eng_b)
    srv_a, ep_a = _start_tcp(inf_a)
    srv_b, ep_b = _start_tcp(inf_b)
    a_stopped = False
    try:
        _slow_decode(monkeypatch, 0.02)
        cli = InferenceClient([ep_a, ep_b], deadline_secs=2.0)
        stream = cli.generate_stream(PROMPT, max_new_tokens=12,
                                     poll_s=0.005)
        got = list(next(stream))
        assert got
        _stop_tcp(srv_a)
        a_stopped = True
        with pytest.raises(ResumedOnNewWeightsError) as ei:
            for chunk in stream:
                got += chunk
        # the caller keeps what epoch-0 delivered and decides itself
        assert ei.value.tokens == got
        assert "epoch" in str(ei.value)
        cli.close()
    finally:
        if not a_stopped:
            _stop_tcp(srv_a)
        _stop_tcp(srv_b)
        inf_a.close()
        inf_b.close()


def test_client_nonstream_typed_errors(gen_frozen, monkeypatch):
    from paddle_tpu.inference import weight_sync as ws

    monkeypatch.setenv(ws.ENV_SYNC, "0")
    _slow_decode(monkeypatch, 0.01)
    eng = _mk_engine(kv=True)
    inf = InferenceServer(gen_frozen, weight_subscribe=False, engine=eng)
    srv, ep = _start_tcp(inf)
    try:
        cli = InferenceClient([ep])
        with pytest.raises(DeadlineExceededError):
            cli.generate(PROMPT, max_new_tokens=56, deadline_ms=80.0)
        # draining replica: admission refused, typed as OverloadedError
        eng.drain(timeout=1.0)
        with pytest.raises(OverloadedError) as ei:
            cli.generate(PROMPT, max_new_tokens=4)
        assert "draining" in str(ei.value)
        cli.close()
    finally:
        _stop_tcp(srv)
        inf.close()


def test_map_app_error_precedence():
    e = _map_app_error(RuntimeError(
        "ResumedOnNewWeights: resume expected weight epoch 0"))
    assert isinstance(e, ResumedOnNewWeightsError) and e.tokens == []
    assert isinstance(_map_app_error(RuntimeError("Overloaded: full")),
                      OverloadedError)
    assert isinstance(
        _map_app_error(RuntimeError("DeadlineExceeded: expired")),
        DeadlineExceededError)
    plain = RuntimeError("boom")
    assert _map_app_error(plain) is plain


# ---------------------------------------------------------------------------
# servetop columns
# ---------------------------------------------------------------------------


def test_servetop_resume_preempt_columns():
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import servetop
    finally:
        sys.path.pop(0)
    rows = [{
        "endpoint": "127.0.0.1:8500",
        "serving": {"served_total": 5, "weight_epoch": 2,
                    "draining": False},
        "generation": {"tokens_total": 640, "tokens_per_s": 123.4,
                       "decode_positions_total": 600,
                       "prefill_positions_total": 40,
                       "recompute_positions_total": 0,
                       "shed_total": 0, "deadline_exceeded_total": 0,
                       "queue_depth": 0,
                       "resumed_total": 7, "preempted_total": 3,
                       "kv_pool": {"residency": 0.42,
                                   "prefix_hit_rate": 0.8}},
    }, {
        "endpoint": "127.0.0.1:8501",  # no engine attached: dashes
        "serving": {"served_total": 1, "weight_epoch": 2},
    }]
    text = servetop.render(rows)
    head = text.splitlines()[0]
    assert "RESUME" in head and "PREEMPT" in head
    line = text.splitlines()[1]
    assert f"{7:6d}" in line and f"{3:7d}" in line
    # the engineless replica dashes the generation columns out
    assert text.splitlines()[2].count("-") >= 6


# ---------------------------------------------------------------------------
# the pool's page size: argument, then the serving env, then the default
# ---------------------------------------------------------------------------


def test_kv_pool_from_budget_page_size_precedence(monkeypatch):
    mk = dict(n_layers=1, kv_heads=2, head_dim=8, n_pages=4,
              allocate=False)
    monkeypatch.delenv(kvmod.ENV_KV_PAGE_SIZE, raising=False)
    assert PagedKVPool.from_budget(**mk).page_size \
        == kvmod._DEFAULT_PAGE_SIZE
    monkeypatch.setenv(kvmod.ENV_KV_PAGE_SIZE, "32")
    assert PagedKVPool.from_budget(**mk).page_size == 32
    assert PagedKVPool.from_budget(page_size=4, **mk).page_size == 4


# ---------------------------------------------------------------------------
# bench goodput-delta fields
# ---------------------------------------------------------------------------


def test_bench_goodput_delta_fields(tmp_path, monkeypatch):
    from paddle_tpu.telemetry import goodput

    sys.path.insert(0, REPO_ROOT)
    try:
        import bench
    finally:
        sys.path.pop(0)
    # ledger off (the default): rows carry NO new fields — bit-identical
    monkeypatch.delenv(goodput.ENV_GATE, raising=False)
    goodput.reset_for_tests()
    assert bench._goodput_snapshot() is None
    assert bench._goodput_fields(None) == {}
    monkeypatch.setenv(goodput.ENV_GATE, "1")
    monkeypatch.setenv(goodput.ENV_DIR, str(tmp_path))
    goodput.reset_for_tests()
    try:
        before = bench._goodput_snapshot()
        assert isinstance(before, dict)
        # the ledger is wall-exact: badput only books against elapsed
        # wall time, so give each note a real window to land in
        time.sleep(0.05)
        goodput.note_serving_badput(30.0, cause="preempt")
        time.sleep(0.05)
        goodput.note_serving_badput(12.0, cause="resume")
        f = bench._goodput_fields(before)
        assert f["goodput_delta_ms"]["serve_preempt"] >= 29.0
        assert f["goodput_delta_ms"]["serve_resume"] >= 11.0
        assert "goodput_ratio" in f
        # zero-delta buckets are dropped from the row, not zero-filled
        assert "serve_shed" not in f["goodput_delta_ms"]
    finally:
        goodput.reset_for_tests()


def test_goodput_preempt_resume_buckets_merge(tmp_path, monkeypatch):
    from paddle_tpu.telemetry import goodput

    monkeypatch.setenv(goodput.ENV_GATE, "1")
    monkeypatch.setenv(goodput.ENV_DIR, str(tmp_path))
    goodput.reset_for_tests()
    try:
        assert "serve_preempt" in goodput.BUCKETS
        assert "serve_resume" in goodput.BUCKETS
        goodput.get_ledger()  # stamp the ledger's birth BEFORE the wait
        time.sleep(0.05)  # wall-exact ledger: badput needs a window
        goodput.note_serving_badput(20.0, cause="preempt")
        time.sleep(0.05)
        goodput.note_serving_badput(10.0, cause="resume")
        s = goodput.get_ledger().summary()
        assert s["buckets_ms"]["serve_preempt"] >= 19.0
        assert s["buckets_ms"]["serve_resume"] >= 9.0
        merged = goodput.merge_fleet({"replica-0": {"goodput": {
            "buckets_ms": {"serve_preempt": 50.0, "serve_resume": 25.0,
                           "productive_step": 900.0}}}})
        assert merged["job"]["badput_ms"]["serve_preempt"] == 50.0
        assert merged["job"]["badput_ms"]["serve_resume"] == 25.0
    finally:
        goodput.reset_for_tests()


# ---------------------------------------------------------------------------
# slow lane: the ci.sh crash-tolerance drills
# ---------------------------------------------------------------------------


def _save_tiny_model(dirname):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        h = layers.fc(x, 16, act="relu")
        pred = layers.fc(h, 4)
    exe = fluid.Executor()
    scope = fluid.executor.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                      main_program=main)


def _spawn_gen_server(model_dir, seed, extra_env=None, timeout=120.0):
    """One real serving process with a generation engine attached."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_SERVE_WEIGHT_SYNC="0", PADDLE_SERVE_GEN="1",
               PADDLE_SERVE_GEN_SEED=str(seed))
    for k in ("PADDLE_PS_FAULT_SPEC", "FLAGS_ps_fault_injection",
              "PADDLE_GOODPUT", "PADDLE_SERVE_RESUME"):
        env.pop(k, None)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "paddle_tpu.inference.server",
         "--model_dir", model_dir, "--port", "0"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=REPO_ROOT)
    deadline = time.time() + timeout
    ep = None
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if "listening on" in line:
            ep = "127.0.0.1:" + line.rsplit(":", 1)[1].strip()
            break
    assert ep, "server never reported its port"
    threading.Thread(target=lambda: [None for _ in proc.stdout],
                     daemon=True).start()
    return proc, ep


def _wait_gen_ready(eps, timeout=90.0):
    from paddle_tpu.distributed.ps_server import _Conn

    deadline = time.time() + timeout
    pending = set(eps)
    while pending and time.time() < deadline:
        for ep in list(pending):
            conn = _Conn(ep, deadline=1.0, io_timeout=5.0)
            try:
                if conn.call("health").get("ok"):
                    pending.discard(ep)
            except Exception:  # noqa: BLE001
                pass
            finally:
                conn.close()
        time.sleep(0.25)
    return not pending


def _kill(proc):
    if proc.poll() is None:
        proc.kill()
    proc.wait(timeout=30)


@pytest.mark.slow
def test_chaos_mid_decode_kill_drill(tmp_path):
    """THE crash-tolerance drill over real processes: two replicas with
    identical weights; one is armed to stall every decode step and then
    hard-die (os._exit) at the 6th — mid-decode, with multiple
    generations in flight. Zero lost generations, the books reconcile
    exactly (accepted == finished, no sheds), and every resumed output
    is bit-identical to the no-fault baseline."""
    model_dir = str(tmp_path / "model")
    _save_tiny_model(model_dir)
    prompts = [PROMPT, [5, 1, 2], [9, 9, 2, 4, 8]]
    maxn = 10

    # no-fault baseline: one clean replica, same seed
    proc, ep = _spawn_gen_server(model_dir, seed=5)
    try:
        assert _wait_gen_ready([ep])
        cli = InferenceClient([ep])
        baseline = [cli.generate(p, max_new_tokens=maxn).tokens
                    for p in prompts]
        cli.close()
    finally:
        _kill(proc)
    assert all(len(t) == maxn for t in baseline)

    # chaos pair: replica A stalls 120ms per decode step (so streams
    # deliver tokens before the cut) and dies at the 6th step
    proc_a, ep_a = _spawn_gen_server(model_dir, seed=5, extra_env={
        "FLAGS_ps_fault_injection": "1",
        "PADDLE_PS_FAULT_SPEC":
            "stall:gen_decode_step:1:120;crash:gen_decode_step:6"})
    proc_b, ep_b = _spawn_gen_server(model_dir, seed=5)
    try:
        assert _wait_gen_ready([ep_a, ep_b])
        resumes0 = _REG.counter("serve_client_stream_resumes_total").value
        cli = InferenceClient([ep_a, ep_b])
        results = [None] * len(prompts)
        blocking = [None]
        errors = []

        def run_stream(i):
            try:
                toks = []
                for chunk in cli.generate_stream(prompts[i],
                                                 max_new_tokens=maxn,
                                                 poll_s=0.02):
                    toks += chunk
                results[i] = toks
            except Exception as e:  # noqa: BLE001 — the drill asserts
                errors.append((i, repr(e)))

        def run_blocking():
            try:
                blocking[0] = cli.generate(prompts[0],
                                           max_new_tokens=maxn).tokens
            except Exception as e:  # noqa: BLE001
                errors.append(("blocking", repr(e)))

        threads = [threading.Thread(target=run_stream, args=(i,))
                   for i in range(len(prompts))]
        threads.append(threading.Thread(target=run_blocking))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads)
        # books reconcile: accepted == finished + explicit sheds, and
        # there were no sheds — nothing lost, nothing double-served
        assert errors == []
        assert results == baseline
        assert blocking[0] == baseline[0]
        # the fault genuinely fired: A hard-died with the crash rule
        assert proc_a.wait(timeout=60) == 1
        # the survivor resumed at least one mid-stream generation with
        # a delivered prefix (the stall guarantees deliveries happened)
        assert _REG.counter("serve_client_stream_resumes_total").value \
            > resumes0
        g = cli.stats()["generation"]
        assert g["resumed_total"] >= 1
        assert g["deadline_exceeded_total"] == 0
        cli.close()
    finally:
        _kill(proc_a)
        _kill(proc_b)


@pytest.mark.slow
def test_kv_pressure_preemption_drill(monkeypatch):
    """Pool exhaustion under a burst: victims are PREEMPTED and
    RESUMED, never deadline-expired; every preempted position is
    matched by a resumed position; and PADDLE_SERVE_RESUME=0 serves
    the identical token streams the r21 FIFO engine produced."""
    shorts = [[40 + i, 3, 7] for i in range(4)]

    def run(resume_on):
        if resume_on:
            monkeypatch.delenv("PADDLE_SERVE_RESUME", raising=False)
        else:
            monkeypatch.setenv("PADDLE_SERVE_RESUME", "0")
        eng = _mk_engine(kv=True, n_pages=PRESSURE_PAGES, queue_depth=8)
        try:
            victim = eng.submit(PROMPT, max_new_tokens=25,
                                deadline_ms=120000.0)
            assert _wait_admitted(eng)
            reqs = [eng.submit(p, max_new_tokens=4,
                               deadline_ms=120000.0) for p in shorts]
            out = [eng.result(r, timeout=180)["tokens"] for r in reqs]
            vtoks = eng.result(victim, timeout=180)["tokens"]
            return vtoks, out, dict(eng.counters)
        finally:
            eng.stop()

    _slow_decode(monkeypatch, 0.004)
    v_on, s_on, c_on = run(resume_on=True)
    v_off, s_off, c_off = run(resume_on=False)
    # resume on: the ladder fired, and the books reconcile exactly —
    # every preemption has a matching resume, position for position
    assert c_on["preempted"] >= 1
    assert c_on["preempted"] == c_on["resumed"]
    assert c_on["preempt_positions"] == c_on["resume_positions"] > 0
    assert c_on["deadline_exceeded"] == 0 and c_on["shed"] == 0
    assert c_on["served"] == 1 + len(shorts)
    # resume off: r21 behavior — pure FIFO, zero preemptions, and the
    # exact same greedy bytes out of every request
    assert c_off["preempted"] == 0 and c_off["resumed"] == 0
    assert c_off["deadline_exceeded"] == 0
    assert v_off == v_on and s_off == s_on
    assert len(v_on) == 25 and all(len(s) == 4 for s in s_on)
