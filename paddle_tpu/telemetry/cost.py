"""Per-op device-time attribution (ISSUE 6): join xplane device events
back to Program IR ops.

The Executor lowers a whole block into ONE jitted XLA computation, so a
step's device time is a single opaque span — PR 4's breakdown says where
the step went (data/compile/device/fetch) but nothing says which op
inside device_ms is hot, which is exactly the visibility gap operator
fusion creates (arXiv:2301.13062). This module closes it:

  1. FLAGS_op_profile makes the Executor wrap each op's lowering in
     jax.named_scope("op<idx>:<type>") (ops/registry.emit_ops), so every
     HLO instruction's op_name metadata carries the Program IR position
     of the op that produced it.
  2. fluid/profiler.xplane_op_events aggregates the device trace's op
     executions by HLO instruction name.
  3. parse_hlo_metadata reads the optimized HLO text
     (Executor.aot_step(...).as_text()) to map instruction -> op_name —
     including the instructions INSIDE fused computations, so an XLA
     fusion covering ops 3..7 is split pro-rata across those scopes and
     marked fused=True instead of being charged to one op.
  4. build_cost_report joins the two through the scope names, rolls the
     rows up per op / op type / user layer call (PR 5's __op_callstack__
     attribution), and derives the measured-MFU gauge.

Measured MFU definition (documented contract, asserted by CI): measured
flops come from the xplane per-op flop counters where the backend
reports them (TPU op profile) and otherwise from XLA's own cost model
(Compiled.cost_analysis()["flops"]); the time base is the ATTRIBUTED
per-step device-op time. The cross-check gauge `formula_mfu` applies
bench.py's closed-form model flops to the SAME time base, so the ratio
measured/formula compares pure flop accounting: XLA counts every
elementwise/normalization op and the exact backward, the model formula
counts 3x the forward matmul/conv MACs — agreement within a factor of 2
is the documented tolerance (typically well inside ±30% on the bench
models).

Everything heavier than stdlib (jax, protobuf) is imported inside
functions: the launcher/pserver processes import paddle_tpu.telemetry
without pulling an accelerator runtime.
"""
from __future__ import annotations

import dataclasses
import json
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

from .registry import get_registry

# "op<idx>:<type>" scope component emitted by ops/registry.emit_ops; the
# FIRST occurrence in an op_name path is the top-level (block 0) op —
# sub-block emitters nest their scopes under the parent op's
_SCOPE_RE = re.compile(r"\bop(\d+):([A-Za-z0-9_.]+)")
# "fwk:<name>" — executor framework compute (rng advance, fetch sync):
# named device time that belongs to no Program op but must not read as
# unattributed mystery
_FWK_RE = re.compile(r"\bfwk:([A-Za-z0-9_.]+)")

# one optimized-HLO instruction: "%name = ..." or "ROOT %name = ..."
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"calls=%([^\s,)]+)")
_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(")


def extract_scope(op_name: str) -> Optional[Tuple[int, str]]:
    """(op index, op type) from an HLO op_name path, or None when the
    instruction was not lowered under an op scope (parameters, infeed,
    runtime-inserted copies)."""
    m = _SCOPE_RE.search(op_name or "")
    if m is None:
        return None
    return int(m.group(1)), m.group(2)


def _any_scope(op_name: str) -> Optional[tuple]:
    """("op", idx, type) | ("fwk", name) | None for an op_name path."""
    sc = extract_scope(op_name)
    if sc is not None:
        return ("op",) + sc
    m = _FWK_RE.search(op_name or "")
    if m is not None:
        return ("fwk", m.group(1))
    return None


_REF_RE = re.compile(r"%([A-Za-z0-9_.\-]+)")


def parse_hlo_metadata(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """instruction name -> {op_name, fusion_calls, scopes} from optimized
    HLO text. `scopes` is the list of scope tuples (("op", idx, type) or
    ("fwk", name)) found on the instruction — for a fusion, the scopes
    of every instruction inside its fused computation (the pro-rata
    split set); for a plain instruction, its own op_name's scope.

    Instructions the backend materialized WITHOUT metadata — layout-
    assignment copies/transposes, rewritten backward convolutions — are
    attributed by graph neighborhood (the grouping XLA's own op profile
    applies): scopes propagate transitively from operands first, then
    from users, so a layout copy feeding a convolution is charged to
    that convolution's op."""
    comps: Dict[str, List[Tuple[str, Optional[tuple]]]] = {}
    instrs: Dict[str, Dict[str, Any]] = {}
    current: Optional[str] = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = _COMP_HEAD_RE.match(line.strip())
            current = head.group(1) if head else None
            if current is not None:
                comps.setdefault(current, [])
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name = m.group(1)
        body = line.split("=", 1)[1] if "=" in line else ""
        opn = _OP_NAME_RE.search(line)
        op_name = opn.group(1) if opn else ""
        scope = _any_scope(op_name)
        if current is not None:
            comps[current].append((name, scope))
        calls = _CALLS_RE.search(line)
        instrs[name] = {
            "op_name": op_name,
            "fusion_calls": calls.group(1) if calls else None,
            "scopes": [scope] if scope else [],
            "operands": [r for r in _REF_RE.findall(body) if r != name],
            "computation": current,
        }
    # resolve fusions: the split set is the multiset of scopes inside the
    # called computation (instruction count is the pro-rata weight — the
    # only weight the HLO text supports uniformly; documented)
    for meta in instrs.values():
        comp = meta["fusion_calls"]
        if comp and comp in comps:
            inner = [s for _n, s in comps[comp] if s is not None]
            if inner:
                meta["scopes"] = inner
    _propagate_scopes(instrs)
    return instrs


def _propagate_scopes(instrs: Dict[str, Dict[str, Any]]) -> None:
    """Transitive neighborhood attribution for metadata-less
    instructions: operands first (a copy BELONGS to what it was copied
    from/for), then users, each to a fixed point. Scope sets acquired
    here are deduplicated — a propagated instruction splits pro-rata
    across its distinct neighboring ops."""
    # same-computation edges only: a fusion body's params don't reference
    # entry instructions by name, so cross-computation noise is already
    # structurally impossible; users is the reverse view
    users: Dict[str, List[str]] = {}
    for name, meta in instrs.items():
        for ref in meta["operands"]:
            if ref in instrs:
                users.setdefault(ref, []).append(name)
    for edges in (lambda n: instrs[n]["operands"],
                  lambda n: users.get(n, ())):
        changed = True
        while changed:
            changed = False
            for name, meta in instrs.items():
                if meta["scopes"]:
                    continue
                found: List[tuple] = []
                for ref in edges(name):
                    other = instrs.get(ref)
                    if other and other["scopes"]:
                        for s in other["scopes"]:
                            if s not in found:
                                found.append(s)
                if found:
                    meta["scopes"] = found
                    changed = True


@dataclasses.dataclass
class CostRow:
    """One attributed op: device time + Program IR identity."""

    scope: str                      # "op<idx>:<type>"
    op_index: int
    op_type: str
    device_ms: float                # total over the profiled window
    share: float                    # of attributed device-op time
    count: int                      # event executions aggregated
    fused: bool                     # any slice arrived via a fusion split
    flops: float = 0.0              # backend-reported, 0 where absent
    bytes_accessed: int = 0         # backend-reported, 0 where absent
    layer: Optional[str] = None     # "file:line in fn" user layer call
    callstack: Optional[tuple] = None

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d.pop("callstack", None)
        return d


@dataclasses.dataclass
class CostReport:
    """The joined profile: per-op rows + rollups + MFU gauges."""

    rows: List[CostRow]
    by_op_type: Dict[str, float]          # op type -> device_ms
    by_layer: Dict[str, float]            # user layer call -> device_ms
    framework: Dict[str, float]           # fwk scope (rng...) -> device_ms
    unattributed: Dict[str, float]        # instr name -> device_ms
    steps: int
    total_op_ms: float                    # all op executions
    attributed_ms: float                  # carried an op scope
    coverage: float                       # attributed / total
    device_ms_per_step: float
    measured_flops_per_step: Optional[float] = None
    formula_flops_per_step: Optional[float] = None
    peak_flops: Optional[float] = None
    measured_mfu: Optional[float] = None
    formula_mfu: Optional[float] = None
    peak_hbm_bytes: Optional[int] = None
    model: Optional[str] = None

    def top(self, k: int = 20) -> List[CostRow]:
        return sorted(self.rows, key=lambda r: -r.device_ms)[:k]

    # -- programmatic per-op queries (tools/op_bench.py reports an op's
    # OWN measured device time, not the whole step's) --
    def rows_for(self, op_type: Optional[str] = None,
                 op_index: Optional[int] = None) -> List[CostRow]:
        """Attributed rows filtered by op type and/or Program IR op
        index (None = don't filter on that axis)."""
        out = []
        for r in self.rows:
            if op_type is not None and r.op_type != op_type:
                continue
            if op_index is not None and r.op_index != op_index:
                continue
            out.append(r)
        return out

    def device_ms_for(self, op_type: Optional[str] = None,
                      op_index: Optional[int] = None,
                      per_step: bool = True) -> float:
        """Total attributed device time (ms) of the matching op scopes —
        per profiled step by default, over the whole window with
        per_step=False. 0.0 when nothing matched (caller decides whether
        to fall back to wall latency)."""
        total = sum(r.device_ms for r in self.rows_for(op_type, op_index))
        return total / self.steps if per_step else total

    def to_json(self, topk: Optional[int] = None) -> dict:
        rows = self.top(topk) if topk else sorted(
            self.rows, key=lambda r: -r.device_ms)
        return {
            "model": self.model,
            "steps": self.steps,
            "total_op_ms": round(self.total_op_ms, 3),
            "attributed_ms": round(self.attributed_ms, 3),
            "coverage": round(self.coverage, 4),
            "device_ms_per_step": round(self.device_ms_per_step, 3),
            "measured_flops_per_step": self.measured_flops_per_step,
            "formula_flops_per_step": self.formula_flops_per_step,
            "peak_flops": self.peak_flops,
            "measured_mfu": self.measured_mfu,
            "formula_mfu": self.formula_mfu,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "by_op_type": {k: round(v, 3) for k, v in sorted(
                self.by_op_type.items(), key=lambda kv: -kv[1])},
            "by_layer": {k: round(v, 3) for k, v in sorted(
                self.by_layer.items(), key=lambda kv: -kv[1])},
            "framework": {k: round(v, 3) for k, v in sorted(
                self.framework.items(), key=lambda kv: -kv[1])},
            "unattributed": {k: round(v, 3) for k, v in sorted(
                self.unattributed.items(), key=lambda kv: -kv[1])[:10]},
            "rows": [r.to_json() for r in rows],
        }

    def format_table(self, topk: int = 20) -> str:
        lines = [
            f"proftop: {self.steps} step(s), "
            f"{self.device_ms_per_step:.3f} ms device-op time/step, "
            f"coverage {100 * self.coverage:.1f}%"
        ]
        if self.measured_mfu is not None:
            lines.append(
                f"measured MFU {self.measured_mfu:.4f}"
                + (f" (model formula {self.formula_mfu:.4f})"
                   if self.formula_mfu is not None else ""))
        lines.append(f"{'op':<34}{'ms':>10}{'share':>8}{'fused':>7}  layer")
        for r in self.top(topk):
            lines.append(
                f"{r.scope[:33]:<34}{r.device_ms:>10.3f}"
                f"{100 * r.share:>7.1f}%{'  yes' if r.fused else '   no':>7}"
                f"  {r.layer or '-'}")
        if self.by_op_type:
            lines.append("-- by op type --")
            for t, ms in sorted(self.by_op_type.items(),
                                key=lambda kv: -kv[1])[:topk]:
                lines.append(f"{t:<34}{ms:>10.3f}")
        return "\n".join(lines)


# last report built in this process — /proftop on the debugz server
_last_report: Optional[CostReport] = None
_last_lock = threading.Lock()


def last_report() -> Optional[CostReport]:
    return _last_report


def _set_last(report: CostReport) -> None:
    global _last_report
    with _last_lock:
        _last_report = report


def _layer_of(op) -> Tuple[Optional[str], Optional[tuple]]:
    """'file:line in fn' of the user's layer call for a Program op, via
    PR 5's __op_callstack__ attribution."""
    cs = op.attrs.get("__op_callstack__") if op is not None else None
    if not cs:
        return None, None
    from ..fluid.analysis import user_frame

    uf = user_frame(cs)
    if uf is None:
        return None, cs
    return f"{uf[0]}:{uf[1]} in {uf[2]}", cs


def build_cost_report(
    op_events: Dict[str, Dict[str, Any]],
    hlo_text: str,
    program=None,
    steps: int = 1,
    measured_flops_per_step: Optional[float] = None,
    formula_flops_per_step: Optional[float] = None,
    peak_flops: Optional[float] = None,
    peak_hbm_bytes: Optional[int] = None,
    model: Optional[str] = None,
) -> CostReport:
    """Join aggregated xplane op executions (profiler.xplane_op_events)
    with the compiled HLO's op_name metadata and the Program IR. Pure
    function over its inputs — tests drive it with synthetic events.
    Publishes the measured-MFU / coverage gauges into the process
    registry and stores the report for the debugz /proftop endpoint."""
    instrs = parse_hlo_metadata(hlo_text) if hlo_text else {}
    steps = max(1, int(steps))

    per_scope: Dict[tuple, Dict[str, Any]] = {}
    framework: Dict[str, float] = {}
    unattributed: Dict[str, float] = {}
    total_ps = 0
    attributed_ps = 0
    for name, ev in op_events.items():
        dur = int(ev.get("dur_ps", 0))
        total_ps += dur
        meta = instrs.get(name)
        scopes = meta["scopes"] if meta else []
        if not scopes:
            unattributed[name] = unattributed.get(name, 0.0) + dur / 1e9
            continue
        attributed_ps += dur
        fused = len(set(scopes)) > 1 or bool(meta.get("fusion_calls"))
        # pro-rata split across the scopes inside the instruction
        # (fusions carry one entry per fused inner instruction, so a
        # scope covering more of the fusion body gets more of its time)
        w = 1.0 / len(scopes)
        for sc in scopes:
            if sc[0] == "fwk":
                framework[sc[1]] = framework.get(sc[1], 0.0) + dur * w / 1e9
                continue
            row = per_scope.setdefault(sc, {
                "dur_ps": 0.0, "count": 0, "fused": False,
                "flops": 0.0, "bytes": 0.0,
            })
            row["dur_ps"] += dur * w
            row["count"] += ev.get("count", 1)
            row["fused"] = row["fused"] or fused
            row["flops"] += float(ev.get("flops", 0.0)) * w
            row["bytes"] += float(ev.get("bytes_accessed", 0)) * w

    block_ops = list(program.global_block().ops) if program is not None else []
    rows: List[CostRow] = []
    by_type: Dict[str, float] = {}
    by_layer: Dict[str, float] = {}
    for (_kind, idx, typ), agg in per_scope.items():
        ms = agg["dur_ps"] / 1e9
        op = block_ops[idx] if 0 <= idx < len(block_ops) else None
        # the scope carries the type it was traced with; a mismatch means
        # the program was rewritten since profiling — keep the traced type
        layer, cs = _layer_of(op)
        rows.append(CostRow(
            scope=f"op{idx}:{typ}", op_index=idx, op_type=typ,
            device_ms=ms,
            share=(agg["dur_ps"] / attributed_ps) if attributed_ps else 0.0,
            count=agg["count"], fused=agg["fused"],
            flops=agg["flops"], bytes_accessed=int(agg["bytes"]),
            layer=layer, callstack=cs,
        ))
        by_type[typ] = by_type.get(typ, 0.0) + ms
        if layer:
            by_layer[layer] = by_layer.get(layer, 0.0) + ms

    total_ms = total_ps / 1e9
    attributed_ms = attributed_ps / 1e9
    device_s_per_step = (attributed_ms / 1e3) / steps
    # xplane per-op flop counters win when the backend stamped any
    # (TPU op profile); otherwise the caller passes XLA's cost model
    if measured_flops_per_step is None:
        xp_flops = sum(r.flops for r in rows)
        if xp_flops > 0:
            measured_flops_per_step = xp_flops / steps
    measured_mfu = formula_mfu = None
    if peak_flops and device_s_per_step > 0:
        if measured_flops_per_step:
            measured_mfu = round(
                measured_flops_per_step / device_s_per_step / peak_flops, 6)
        if formula_flops_per_step:
            formula_mfu = round(
                formula_flops_per_step / device_s_per_step / peak_flops, 6)

    report = CostReport(
        rows=rows, by_op_type=by_type, by_layer=by_layer,
        framework=framework, unattributed=unattributed, steps=steps,
        total_op_ms=total_ms, attributed_ms=attributed_ms,
        coverage=(attributed_ms / total_ms) if total_ms else 0.0,
        device_ms_per_step=attributed_ms / steps,
        measured_flops_per_step=measured_flops_per_step,
        formula_flops_per_step=formula_flops_per_step,
        peak_flops=peak_flops,
        measured_mfu=measured_mfu, formula_mfu=formula_mfu,
        peak_hbm_bytes=peak_hbm_bytes, model=model,
    )
    reg = get_registry()
    reg.gauge("op_profile_coverage",
              help="fraction of device-op time attributed to op scopes"
              ).set(report.coverage)
    reg.gauge("op_profile_device_ms_per_step",
              help="attributed device-op time per profiled step (ms)"
              ).set(report.device_ms_per_step)
    if measured_mfu is not None:
        reg.gauge("measured_mfu",
                  help="measured flops / attributed device time / peak "
                       "(xplane counters or XLA cost model; see "
                       "telemetry/cost.py for the definition)"
                  ).set(measured_mfu)
    _set_last(report)
    return report


def profile_executor_run(exe, program, feed, fetch_list, scope=None,
                         steps: int = 3, warmup: int = 1,
                         formula_flops_per_step: Optional[float] = None,
                         peak_flops: Optional[float] = None,
                         model: Optional[str] = None) -> CostReport:
    """End-to-end per-op profile of an Executor step: enable
    FLAGS_op_profile, warm the compile cache, trace `steps` runs under
    the jax profiler, AOT-recover the optimized HLO (one extra compile —
    diagnostics pricing), and join everything into a CostReport.
    tools/proftop.py and bench.py's BENCH_OP_PROFILE hook both ride
    this."""
    import shutil
    import tempfile

    from ..fluid import flags
    from ..fluid import monitor
    from ..fluid import profiler as prof

    prev = flags.get_flags("FLAGS_op_profile")["FLAGS_op_profile"]
    flags.set_flags({"FLAGS_op_profile": True})
    trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_cost_")
    try:
        import jax

        for _ in range(max(1, warmup)):
            out = exe.run(program, feed=feed, fetch_list=fetch_list,
                          scope=scope)
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(steps):
                out = exe.run(program, feed=feed, fetch_list=fetch_list,
                              scope=scope, return_numpy=False)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        compiled = exe.aot_step(program, feed=feed, fetch_list=fetch_list,
                                scope=scope)
        hlo_text = compiled.as_text()
        measured = _cost_analysis_flops(compiled)
        if peak_flops is None and jax.default_backend() == "tpu":
            # off the chip there is no peak to divide by: the report's
            # measured_mfu / formula_mfu stay unset
            peak_flops = peak_flops_per_chip()
        return build_cost_report(
            prof.xplane_op_events(trace_dir), hlo_text,
            program=program if not hasattr(program, "_program")
            else program._program,
            steps=steps,
            measured_flops_per_step=measured,
            formula_flops_per_step=formula_flops_per_step,
            peak_flops=peak_flops,
            peak_hbm_bytes=monitor.peak_hbm_bytes() or None,
            model=model,
        )
    finally:
        flags.set_flags({"FLAGS_op_profile": prev})
        shutil.rmtree(trace_dir, ignore_errors=True)


def _cost_analysis_flops(compiled) -> Optional[float]:
    """Per-execution flops from XLA's cost model; None when the model
    counted none."""
    return float(compiled.cost_analysis().get("flops", 0.0)) or None


# bf16 peak FLOP/s per chip, keyed by a substring of the lowercased
# jax device_kind (vendor datasheets; v5e: Google Cloud "TPU v5e" page)
PEAK_BF16_FLOPS = {
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6": 918e12,  # trillium
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops_per_chip() -> float:
    """bf16 peak FLOP/s for the local chip. THE table — bench.py
    delegates here so the MFU denominators of the bench rows and the
    measured gauge can never drift apart. A device that is not in the
    table (the CPU included) raises: a utilization against a guessed
    peak is not a measurement."""
    import jax

    kind = jax.devices()[0].device_kind
    for k, v in PEAK_BF16_FLOPS.items():
        if k in kind.lower():
            return v
    raise ValueError(
        f"peak_flops_per_chip: device_kind {kind!r} is not in the peak "
        f"table {sorted(PEAK_BF16_FLOPS)}; add its datasheet peak or pass "
        f"peak_flops= explicitly")


def report_to_json_line(report: CostReport, topk: Optional[int] = None) -> str:
    return json.dumps(report.to_json(topk))
