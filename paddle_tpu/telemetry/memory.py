"""Per-op / per-variable HBM attribution + OOM doctor (ISSUE 11).

The observability stack answers "where did the TIME go" (cost.py /
proftop); this module answers "where did the MEMORY go" — the question
behind every OOM, every remat decision, and the SPMD items (which
must rank candidates by fit before ranking them by speed):

  1. The static side: fluid/analysis/liverange.py computes first-def/
     last-use, byte size and category (params / optimizer_state /
     gradients / feeds / activations) per Variable, plus the peak
     simultaneous-bytes estimate with donation awareness.
  2. The measured side: Executor.aot_step(...).memory_analysis() is
     XLA's buffer-assignment truth (argument/output/temp/alias bytes,
     peak), and the optimized HLO text — compiled under FLAGS_op_profile
     so instruction metadata carries "op<idx>:<type>" scopes — lets temp
     buffers join back to IR ops through cost.py's scope machinery
     (parse_hlo_metadata: fusion splits + neighborhood propagation).
  3. The join: build_memory_report cross-checks static vs measured
     (documented tolerance below), computes attribution COVERAGE
     (fraction of XLA's peak the layer can assign to IR ops), and ranks
     buffers with PR-5 user callstacks.

Surfaces: debugz /memz (live per-category breakdown + per-device
allocator stats), tools/memtop.py (CLI, --budget gate), bench.py
(peak_hbm_bytes / hbm_model_bytes row fields), and the OOM DOCTOR —
Executor catches RESOURCE_EXHAUSTED at compile and run time (plus the
deterministic `oom:<phase>:<nth>` fault rule and the
PADDLE_HBM_BUDGET_BYTES proactive gate), builds a memory flight-record
(largest live buffers at the static high-water point, owning op + user
layer, concrete what-ifs) and dumps it through the PR-9 flight-recorder
path (PADDLE_TRACE_DIR/memrec.<tag>.json) before raising HBMOOMError.

Cost contract: with FLAGS_mem_profile unset (the default) nothing here
runs on the step path — step records, wire bytes and the loss trace are
bit-identical (asserted by test). Flag on: one static live-range pass
per (program, feed-signature) compile miss — microseconds of host time,
no device work, no extra compile. The measured join (one AOT compile)
is diagnostics pricing: memtop, bench hooks, explicit calls.

Static-vs-measured tolerance (documented contract): XLA fusion deletes
activations the IR names (an elementwise chain never materializes) and
buffer assignment reuses dead buffers, so the static estimate runs HIGH
on activation-heavy graphs; XLA also pads and adds workspace the IR
cannot see, which runs it LOW on tiny graphs. The cross-check asserts
static/measured within [0.3, 3.0] on the bench models; coverage (the
CI bar) is measured-side and must be >= 0.9.

Everything heavier than stdlib+numpy (jax) is imported inside
functions: pservers and the launcher import paddle_tpu.telemetry
without an accelerator runtime.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from .registry import get_registry

ENV_BUDGET = "PADDLE_HBM_BUDGET_BYTES"

# "f32[8,16]{1,0}" / "bf16[2,3,4]" / "u32[]" — the result shape an HLO
# instruction materializes; element bit-widths for buffer sizing
_SHAPE_RE = re.compile(r"^\s*(?:ROOT\s+)?%[^\s=]+\s*=\s*"
                       r"(?:\()?([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BITS = {
    "pred": 8, "s8": 8, "u8": 8, "s16": 16, "u16": 16, "f16": 16,
    "bf16": 16, "s32": 32, "u32": 32, "f32": 32, "s64": 64, "u64": 64,
    "f64": 64, "c64": 64, "c128": 128, "f8e4m3fn": 8, "f8e5m2": 8,
}

# substrings that identify an allocator / compile-time OOM across jax
# versions and backends (XlaRuntimeError stringifies the status code)
_OOM_MARKS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
              "Out of memory", "out of memory", "OOM",
              "Attempting to allocate")


class HBMOOMError(RuntimeError):
    """An HBM out-of-memory, enriched by the OOM doctor: carries the
    structured report (largest live buffers at the high-water point,
    owning op + user layer, what-ifs) and the memrec dump path."""

    def __init__(self, message: str, report: Optional[dict] = None,
                 dump_path: Optional[str] = None):
        super().__init__(message)
        self.report = report or {}
        self.dump_path = dump_path


def is_oom(exc: BaseException) -> bool:
    """Does this exception smell like an allocator/compile-time OOM?"""
    s = f"{type(exc).__name__}: {exc}"
    return any(m in s for m in _OOM_MARKS)


def hbm_budget_bytes() -> Optional[int]:
    """PADDLE_HBM_BUDGET_BYTES — the operator's declared per-device
    ceiling (CI gates, shared-chip etiquette). None when unset."""
    raw = os.environ.get(ENV_BUDGET)
    if not raw:
        return None
    try:
        v = int(float(raw))
    except ValueError:
        return None
    return v if v > 0 else None


# ---------------------------------------------------------------------------
# measured side: HLO buffer attribution
# ---------------------------------------------------------------------------


def _instr_bytes(line: str) -> int:
    """Byte size of the buffer an HLO instruction line defines; 0 for
    unparseable/tuple shapes (tuples own no bytes themselves)."""
    m = _SHAPE_RE.match(line)
    if m is None:
        return 0
    bits = _DTYPE_BITS.get(m.group(1))
    if bits is None:
        return 0
    n = 1
    dims = m.group(2)
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return (n * bits + 7) // 8


def attribute_hlo_buffers(hlo_text: str) -> Dict[str, Any]:
    """Join every HLO instruction's output-buffer size to its IR op
    scope (cost.parse_hlo_metadata: op_name metadata, fusion splits,
    operand/user propagation). Returns per-op byte rollups plus the
    scoped fraction — the number that says how much of XLA's temp
    traffic the attribution layer can NAME. Entry parameters are
    excluded (they are the argument buffers, attributed by name on the
    static side)."""
    from . import cost

    instrs = cost.parse_hlo_metadata(hlo_text) if hlo_text else {}
    # size only ENTRY-computation instructions: a fused computation's
    # internals live in registers/scratch — its ROOT is the fusion
    # instruction's own buffer, already sized at the call site (sizing
    # both would double-count every fusion)
    sizes: Dict[str, int] = {}
    in_entry = False
    for line in (hlo_text or "").splitlines():
        if line and not line[0].isspace():
            in_entry = line.lstrip().startswith("ENTRY")
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=", line)
        if m is None or "parameter(" in line or not in_entry:
            continue
        sizes[m.group(1)] = _instr_bytes(line)

    per_op: Dict[str, Dict[str, Any]] = {}
    scoped = 0
    total = 0
    for name, nbytes in sizes.items():
        if not nbytes:
            continue
        total += nbytes
        meta = instrs.get(name)
        scopes = [s for s in (meta["scopes"] if meta else ())
                  if s and s[0] == "op"]
        if not scopes:
            continue
        scoped += nbytes
        w = nbytes / len(scopes)
        for _kind, idx, typ in scopes:
            key = f"op{idx}:{typ}"
            row = per_op.setdefault(key, {"op_index": idx, "op_type": typ,
                                          "bytes": 0.0, "instrs": 0})
            row["bytes"] += w
            row["instrs"] += 1
    for row in per_op.values():
        row["bytes"] = int(row["bytes"])
    return {
        "per_op": per_op,
        "scoped_bytes": int(scoped),
        "total_bytes": int(total),
        "scoped_fraction": (scoped / total) if total else 0.0,
    }


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MemoryReport:
    """The joined picture: static live ranges + measured buffer
    assignment + attribution coverage + what-ifs."""

    model: Optional[str]
    static: Any                       # LiveRangeAnalysis
    measured: Optional[dict] = None   # Executor.memory_analysis() dict
    hlo_attr: Optional[dict] = None   # attribute_hlo_buffers() result
    coverage: Optional[float] = None  # attributed / measured peak
    static_over_measured: Optional[float] = None
    what_ifs: List[dict] = dataclasses.field(default_factory=list)
    budget_bytes: Optional[int] = None

    @property
    def peak_bytes(self) -> int:
        """The best available peak: measured when present, else static."""
        if self.measured and self.measured.get("peak_bytes"):
            return int(self.measured["peak_bytes"])
        return int(self.static.peak_bytes)

    def over_budget(self) -> bool:
        return (self.budget_bytes is not None
                and self.static.peak_bytes > self.budget_bytes)

    def top(self, k: int = 20, live_at_peak_only: bool = False):
        return self.static.top(k, live_at_peak_only=live_at_peak_only)

    def to_json(self, topk: Optional[int] = None) -> dict:
        st = self.static
        out = {
            "model": self.model,
            "static_peak_bytes": int(st.peak_bytes),
            "measured_peak_bytes": (int(self.measured["peak_bytes"])
                                    if self.measured else None),
            "static_over_measured": self.static_over_measured,
            "coverage": (round(self.coverage, 4)
                         if self.coverage is not None else None),
            "budget_bytes": self.budget_bytes,
            "over_budget": self.over_budget(),
            "model_bytes": int(st.model_bytes),
            "resident_bytes": int(st.resident_bytes),
            "batch_hint": st.batch_hint,
            "n_ops": st.n_ops,
            "peak_op_index": st.peak_op_index,
            "peak_op_type": st.peak_op_type,
            "peak_layer": st.peak_layer,
            "categories": dict(st.categories),
            "categories_at_peak": dict(st.categories_at_peak),
            "unsized": list(st.unsized),
            "what_ifs": list(self.what_ifs),
            "buffers": [b.to_json() for b in st.top(topk or 20)],
            "live_at_peak": [b.to_json()
                             for b in st.top(topk or 20,
                                             live_at_peak_only=True)],
        }
        if self.measured:
            out["measured"] = {k: int(v) for k, v in self.measured.items()}
        if self.hlo_attr:
            out["hlo_temp_attribution"] = {
                "scoped_fraction": round(
                    self.hlo_attr["scoped_fraction"], 4),
                "per_op": dict(sorted(
                    self.hlo_attr["per_op"].items(),
                    key=lambda kv: -kv[1]["bytes"])[:topk or 20]),
            }
        return out

    def format_table(self, topk: int = 20) -> str:
        st = self.static
        lines = [
            f"memtop: static peak {_fmt_bytes(st.peak_bytes)}"
            + (f", measured peak {_fmt_bytes(self.measured['peak_bytes'])}"
               f" (static/measured "
               f"{self.static_over_measured:.2f}x)"
               if self.measured and self.static_over_measured else "")
            + (f", coverage {100 * self.coverage:.1f}%"
               if self.coverage is not None else ""),
            "-- categories (total / live at peak) --",
        ]
        for c, v in sorted(st.categories.items(), key=lambda kv: -kv[1]):
            lines.append(f"{c:<18}{_fmt_bytes(v):>12}"
                         f"{_fmt_bytes(st.categories_at_peak[c]):>12}")
        if self.budget_bytes is not None:
            verdict = "OVER" if self.over_budget() else "ok"
            lines.append(f"budget {_fmt_bytes(self.budget_bytes)}: "
                         f"{verdict}")
        lines.append(
            f"high-water at op#{st.peak_op_index}"
            f" [{st.peak_op_type or '?'}]"
            + (f" ({st.peak_layer})" if st.peak_layer else ""))
        lines.append(f"{'buffer':<34}{'bytes':>12}{'cat':>17}"
                     f"{'range':>12}  layer")
        for b in st.top(topk, live_at_peak_only=True):
            lines.append(
                f"{b.name[:33]:<34}{_fmt_bytes(b.bytes):>12}"
                f"{b.category:>17}{f'{b.first_def}..{b.last_use}':>12}"
                f"  {b.layer or '-'}")
        for w in self.what_ifs:
            lines.append(f"what-if: {w['text']}")
        return "\n".join(lines)


def _fmt_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"


# last report built in this process — the debugz /memz endpoint
_last_report: Optional[MemoryReport] = None
_last_lock = threading.Lock()
_memz_key = None  # (serial, version, feed-sig) the last report covers


def last_report() -> Optional[MemoryReport]:
    return _last_report


def _set_last(report: MemoryReport) -> None:
    global _last_report
    with _last_lock:
        _last_report = report


def _reset_for_tests() -> None:
    global _last_report, _memz_key
    with _last_lock:
        _last_report = None
        _memz_key = None


# ---------------------------------------------------------------------------
# what-ifs
# ---------------------------------------------------------------------------


def _local_device_count() -> int:
    try:
        import jax

        return max(1, jax.local_device_count())
    except Exception:  # noqa: BLE001 — doctor must work without a device
        return 1


def compute_what_ifs(static, limit_bytes: Optional[int] = None
                     ) -> List[dict]:
    """Concrete levers, ranked by saved bytes: remat the fattest
    activation block, shard the fattest parameter, shrink the batch to
    fit. Estimates ride the static model (documented: upper-bound
    flavored), which is exactly what an OOM victim needs first."""
    out: List[dict] = []
    live = {b.name for b in static.buffers} & set(static.live_at_peak)
    by_name = static.by_name()
    peak = static.peak_bytes

    # remat: group live-at-peak activations by user layer; recomputing
    # the fattest block frees its bytes at the high-water point
    layers: Dict[str, int] = {}
    for n in live:
        b = by_name[n]
        if b.category == "activations" and b.first_def >= 0:
            layers[b.layer or "<unattributed>"] = (
                layers.get(b.layer or "<unattributed>", 0) + b.bytes)
    if layers:
        layer, saved = max(layers.items(), key=lambda kv: kv[1])
        out.append({
            "action": "remat", "target": layer, "saves_bytes": int(saved),
            "text": f"remat the block at {layer} "
                    f"(saves ~{_fmt_bytes(saved)} at the high-water "
                    f"point)"})

    # shard: the fattest parameter split over the local devices
    params = [b for b in static.buffers if b.category == "params"]
    n_dev = _local_device_count()
    shard_over = n_dev if n_dev > 1 else 2
    if params:
        fat = max(params, key=lambda b: b.bytes)
        saved = fat.bytes * (shard_over - 1) // shard_over
        if saved > 0:
            out.append({
                "action": "shard", "target": fat.name,
                "saves_bytes": int(saved),
                "text": f"shard param {fat.name!r} axis 0 over "
                        f"{shard_over} devices (saves "
                        f"~{_fmt_bytes(saved)} per device)"})

    # batch: solve fixed + (N/B) * batch_dep <= limit for N
    if limit_bytes and static.batch_hint:
        batch_dep = sum(b.bytes for n in live
                        if (b := by_name[n]).batch_scaled)
        fixed = peak - batch_dep
        if batch_dep > 0 and fixed < limit_bytes:
            n_fit = int(static.batch_hint
                        * (limit_bytes - fixed) / batch_dep)
            if 0 < n_fit < static.batch_hint:
                out.append({
                    "action": "batch", "target": n_fit,
                    "saves_bytes": int(peak - fixed
                                       - batch_dep * n_fit
                                       / static.batch_hint),
                    "text": f"batch {n_fit} fits the "
                            f"{_fmt_bytes(limit_bytes)} budget "
                            f"(currently {static.batch_hint})"})
    out.sort(key=lambda w: -(w.get("saves_bytes") or 0))
    return out


# ---------------------------------------------------------------------------
# building reports
# ---------------------------------------------------------------------------


def build_memory_report(
    program,
    feed_shapes: Optional[Dict[str, Any]] = None,
    fetch_names=(),
    measured: Optional[dict] = None,
    hlo_text: Optional[str] = None,
    model: Optional[str] = None,
    budget_bytes: Optional[int] = None,
    publish: bool = True,
) -> MemoryReport:
    """Pure join of the static pass with whatever measured inputs the
    caller has (tests drive it with synthetic pieces). Publishes the
    gauges + /memz report and emits a kind="mem_report" sink record
    unless publish=False.

    Coverage definition (the CI bar): of XLA's measured peak
    (arguments + outputs + temps - aliased), the argument/output slice
    is attributed by NAME through the static pass (state and feed vars
    are exactly sizeable), and the temp slice is attributed through the
    HLO op-scope join — coverage = (min(args+outs-alias, static
    name-attributed bytes) + scoped_temp_fraction * temps) / peak."""
    from ..fluid.analysis import analyze_live_ranges

    shapes = {}
    batch_hint = None
    for n, a in (feed_shapes or {}).items():
        shp = tuple(getattr(a, "shape", a) or ())
        shapes[n] = shp
    static = analyze_live_ranges(
        program, feed_names=set(shapes), fetch_names=set(fetch_names),
        shapes=shapes, batch_hint=batch_hint)

    hlo_attr = attribute_hlo_buffers(hlo_text) if hlo_text else None
    coverage = None
    ratio = None
    if measured and measured.get("peak_bytes"):
        peak = int(measured["peak_bytes"])
        # argument/output buffers ARE named program variables (feeds,
        # state, fetches) — attributed by name via the static pass by
        # construction; the temp slice is attributed op-by-op through
        # the HLO scope join, discounted by its unscoped fraction
        args_outs = (measured.get("argument_size_in_bytes", 0)
                     + measured.get("output_size_in_bytes", 0)
                     - measured.get("alias_size_in_bytes", 0))
        covered = float(args_outs)
        temps = measured.get("temp_size_in_bytes", 0)
        if hlo_attr is not None:
            covered += temps * hlo_attr["scoped_fraction"]
        coverage = min(1.0, covered / peak) if peak else 0.0
        ratio = round(static.peak_bytes / peak, 4) if peak else None

    report = MemoryReport(
        model=model, static=static, measured=measured, hlo_attr=hlo_attr,
        coverage=coverage, static_over_measured=ratio,
        budget_bytes=budget_bytes if budget_bytes is not None
        else hbm_budget_bytes(),
    )
    report.what_ifs = compute_what_ifs(
        static, limit_bytes=report.budget_bytes
        or (measured or {}).get("peak_bytes"))
    if publish:
        _publish(report)
    return report


def _publish(report: MemoryReport) -> None:
    reg = get_registry()
    st = report.static
    reg.gauge("hbm_static_peak_bytes",
              help="static live-range peak estimate (bytes)"
              ).set(st.peak_bytes)
    reg.gauge("hbm_model_bytes",
              help="params + optimizer state (bytes)").set(st.model_bytes)
    for cat, v in st.categories.items():
        reg.gauge("hbm_category_bytes",
                  help="static bytes per category",
                  category=cat).set(v)
    if report.coverage is not None:
        reg.gauge("hbm_attribution_coverage",
                  help="fraction of XLA's measured peak attributed to "
                       "IR ops / named state").set(report.coverage)
    _set_last(report)
    try:
        from . import sink

        sink.emit({"kind": "mem_report",
                   "model": report.model,
                   "static_peak_bytes": int(st.peak_bytes),
                   "measured_peak_bytes": (
                       int(report.measured["peak_bytes"])
                       if report.measured else None),
                   "model_bytes": int(st.model_bytes),
                   "coverage": report.coverage,
                   "categories": dict(st.categories)})
    except Exception:  # noqa: BLE001 — diagnostics never fail the caller
        pass


def profile_executor_memory(exe, program, feed, fetch_list, scope=None,
                            model: Optional[str] = None,
                            budget_bytes: Optional[int] = None,
                            ) -> MemoryReport:
    """The full measured join for a runnable step: XLA memory_analysis
    + optimized-HLO buffer attribution (compiled under FLAGS_op_profile
    so instructions carry op scopes) + the static pass. One extra AOT
    compile — diagnostics pricing (memtop, bench hooks), never the step
    path."""
    from ..fluid import flags

    if hasattr(program, "_program"):
        program = program._program
    prev = flags.get_flags("FLAGS_op_profile")["FLAGS_op_profile"]
    flags.set_flags({"FLAGS_op_profile": True})
    try:
        compiled = exe.aot_step(program, feed=feed, fetch_list=fetch_list,
                                scope=scope)
        hlo_text = compiled.as_text()
        measured = exe.memory_analysis(program, feed=feed,
                                       fetch_list=fetch_list, scope=scope)
    finally:
        flags.set_flags({"FLAGS_op_profile": prev})
    from ..fluid import framework as _fw

    fetch_names = [v.name if isinstance(v, _fw.Variable) else str(v)
                   for v in (fetch_list or [])]
    return build_memory_report(
        program, feed_shapes=dict(feed or {}), fetch_names=fetch_names,
        measured=measured, hlo_text=hlo_text, model=model,
        budget_bytes=budget_bytes)


# ---------------------------------------------------------------------------
# executor hooks: FLAGS_mem_profile + budget gate + OOM doctor
# ---------------------------------------------------------------------------


def on_compile(program, feed_arrays, fetch_names) -> None:
    """Called by Executor._ensure_compiled on every compile-cache MISS.
    Flag-off AND budget-unset: one flag read + one env read, nothing
    else (the bit-identity contract). FLAGS_mem_profile on: run the
    static pass, publish gauges + /memz + the kind="mem_report" record.
    PADDLE_HBM_BUDGET_BYTES set: gate the static estimate against the
    budget BEFORE paying (or failing) the XLA compile."""
    from ..fluid.flags import flag

    budget = hbm_budget_bytes()
    if not flag("FLAGS_mem_profile") and budget is None:
        return
    global _memz_key
    try:
        report = build_memory_report(
            program, feed_shapes=feed_arrays, fetch_names=fetch_names,
            budget_bytes=budget)
        _memz_key = (program._serial, program._version)
    except Exception:  # noqa: BLE001 — diagnostics never fail a compile
        return
    if budget is not None and report.static.peak_bytes > budget:
        raise_oom(
            program, feed_arrays, phase="budget", report=report,
            message=(
                f"static HBM estimate "
                f"{_fmt_bytes(report.static.peak_bytes)} exceeds "
                f"PADDLE_HBM_BUDGET_BYTES={_fmt_bytes(budget)}"))


def raise_oom(program, feed_arrays, phase: str,
              error: Optional[BaseException] = None,
              report: Optional[MemoryReport] = None,
              message: Optional[str] = None) -> None:
    """The OOM doctor: build the static report (no device work — the
    device just refused us), dump the memory flight-record through the
    PR-9 flight-recorder path, and raise HBMOOMError naming the largest
    live buffer at the high-water point and the concrete what-ifs."""
    if report is None:
        try:
            report = build_memory_report(
                program, feed_shapes=feed_arrays, publish=False)
        except Exception:  # noqa: BLE001 — a broken doctor must not mask
            report = None  # the original OOM
    doc = _doctor_payload(report, phase, error, message)
    path = dump_memrec(doc)
    get_registry().counter(
        "hbm_oom_total", help="OOMs caught by the doctor",
        phase=phase).inc()
    try:
        from . import tracing

        tracing.annotate(oom_phase=phase)
        tracing.flight_dump(f"oom:{phase}")
    except Exception:  # noqa: BLE001
        pass
    lines = [message or f"HBM out of memory at {phase}"]
    if report is not None:
        st = report.static
        lines.append(
            f"  static peak {_fmt_bytes(st.peak_bytes)} at "
            f"op#{st.peak_op_index} [{st.peak_op_type or '?'}]"
            + (f" ({st.peak_layer})" if st.peak_layer else ""))
        for b in st.top(3, live_at_peak_only=True):
            lines.append(
                f"  {b.name}: {_fmt_bytes(b.bytes)} [{b.category}]"
                + (f" at {b.layer}" if b.layer else ""))
        for w in report.what_ifs[:3]:
            lines.append(f"  what-if: {w['text']}")
    if path:
        lines.append(f"  memory flight-record: {path}")
    raise HBMOOMError("\n".join(lines),
                      report=doc, dump_path=path) from error


def _doctor_payload(report: Optional[MemoryReport], phase: str,
                    error: Optional[BaseException],
                    message: Optional[str]) -> dict:
    doc: Dict[str, Any] = {
        "format": 1,
        "kind": "oom",
        "phase": phase,
        "ts": round(time.time(), 6),
        "pid": os.getpid(),
        "message": message or (f"{type(error).__name__}: {error}"
                               if error else "out of memory"),
        "budget_bytes": hbm_budget_bytes(),
    }
    if report is not None:
        st = report.static
        doc["report"] = report.to_json(topk=20)
        top = st.top(1, live_at_peak_only=True)
        if top:
            doc["culprit"] = top[0].to_json()
    try:
        from ..fluid import monitor

        doc["devices"] = monitor.device_memory_stats()
    except Exception:  # noqa: BLE001
        doc["devices"] = []
    return doc


def dump_memrec(payload: dict, directory: Optional[str] = None
                ) -> Optional[str]:
    """Atomically write the memory flight-record next to the tracing
    flight recorder's dumps: PADDLE_TRACE_DIR/memrec.<tag>.json. Unlike
    span dumps this does NOT require PADDLE_TRACING — an OOM post-mortem
    is useful without causal tracing armed. None when no directory is
    configured (nothing to do) or the disk refuses (a full disk must
    not mask the OOM)."""
    from . import tracing

    directory = directory or os.environ.get(tracing.ENV_DIR)
    if not directory:
        return None
    path = os.path.join(directory,
                        f"memrec.{tracing.process_tag()}.json")
    try:
        os.makedirs(directory, exist_ok=True)
        tracing._atomic_write(path, json.dumps(payload).encode())
    except OSError:
        return None
    return path


# ---------------------------------------------------------------------------
# debugz /memz
# ---------------------------------------------------------------------------


#: extra /memz sections registered by subsystems that own big standing
#: allocations (e.g. the serving KV pool) — name -> zero-arg callable
#: returning a JSON-able dict.  A section that raises is reported as an
#: error string instead of killing the page.
_MEMZ_SECTIONS: Dict[str, Callable[[], dict]] = {}


def register_memz_section(name: str, fn: Callable[[], dict]) -> None:
    """Attach a named section to the /memz payload (idempotent: the
    latest registration under a name wins)."""
    _MEMZ_SECTIONS[name] = fn


def unregister_memz_section(name: str) -> None:
    _MEMZ_SECTIONS.pop(name, None)


def memz(topk: int = 20) -> dict:
    """The /memz payload: last memory report (per-category breakdown,
    top-K buffers with callstacks) + LIVE per-device allocator stats —
    works report-less too (the live view is always available)."""
    from ..fluid.flags import flag

    devices: List[dict] = []
    try:
        from ..fluid import monitor

        devices = monitor.device_memory_stats()
    except Exception:  # noqa: BLE001 — report pages never crash
        pass
    rep = last_report()
    out = {
        "enabled": bool(flag("FLAGS_mem_profile")),
        "budget_bytes": hbm_budget_bytes(),
        "devices": devices,
        "report": rep.to_json(topk) if rep is not None else None,
    }
    for name, fn in list(_MEMZ_SECTIONS.items()):
        try:
            out[name] = fn()
        except Exception as e:  # noqa: BLE001 — report pages never crash
            out[name] = {"error": f"{type(e).__name__}: {e}"}
    return out
