"""The yardstick's arithmetic on the CPU: trace reduction, the join to the
compiled step's text, the kernels' FLOP and byte functions, the peaks."""
import os

import pytest

from benchmark import hlo_text, manifest
from benchmark import trace_reduce as tr
from benchmark.trace_reduce import Event, Line, Plane

FIXTURE = os.path.join(manifest.BENCH_DIR, "fixtures",
                       "bert_s512_step.json.gz")
# the round-5 tree lowered its Pallas calls through `closed_call`; the
# result shapes in the trace's names say which is which
OLD_NAMES = {"closed_call.174": "flash_bsh_fwd",  # -> (o, lse [64,12,512])
             "closed_call.171": "flash_bsh_bwd"}


@pytest.fixture(scope="module")
def step():
    (device,) = tr.load_chrome(FIXTURE)
    span = device.line("Steps").events[0]
    return device, (span.start, span.end)


def test_fixture_busy_share_and_step_time(step):
    device, window = step
    red = tr.reduce_device(device, window)
    assert red.window_ns * 1e-6 == pytest.approx(203.085, abs=0.01)
    assert red.busy_ns / red.window_ns == pytest.approx(0.9993, abs=2e-4)
    assert red.idle_share == pytest.approx(0.0007, abs=2e-4)


def test_self_times_sum_to_busy_time_under_nesting(step):
    device, window = step
    red = tr.reduce_device(device, window)
    assert sum(red.self_ns_by_name.values()) == pytest.approx(
        red.busy_ns, rel=1e-9)
    # the two layer scans hold their bodies: 70.2 and 114.9 ms of duration,
    # microseconds of self time
    nodes = tr.nest(tr.clip(device.line(tr.OPS_LINE).events, window))
    whiles = [n for n in nodes if n.event.name.startswith("%while")]
    assert sorted(round(n.event.duration * 1e-6, 1) for n in whiles) == [
        70.2, 114.9]
    assert all(n.self_ns < 0.1e6 and not n.leaf for n in whiles)
    # what ran inside them is counted under its own name, once
    assert red.self_ns_by_name["compare_select_fusion.24"] * 1e-6 == (
        pytest.approx(20.10, abs=0.01))
    assert red.calls_by_name["compare_select_fusion.24"] == 12


def test_kernel_sum_by_name_from_inside_the_scan(step):
    device, window = step
    red = tr.reduce_device(device, window, OLD_NAMES.get)
    assert red.kernel_ns["flash_bsh_fwd"] * 1e-6 == pytest.approx(
        10.43, abs=0.01)
    assert red.kernel_ns["flash_bsh_bwd"] * 1e-6 == pytest.approx(
        18.35, abs=0.01)
    assert [len(red.kernel_calls[k]) for k in sorted(red.kernel_calls)] == [
        12, 12]
    assert "closed_call.174" not in red.self_ns_by_name
    # 14.2 % of the step: the share ROADMAP.md S3 quotes for s512
    share = sum(red.kernel_ns.values()) / red.window_ns
    assert share == pytest.approx(0.142, abs=0.002)


def _two_devices():
    ms = 1e6
    dev0 = Plane("/device:TPU:0", [
        Line(tr.OPS_LINE, [
            Event("while.1", 0 * ms, 200 * ms),
            Event("fusion.1", 0 * ms, 100 * ms),
            Event("all-reduce-start.1", 100 * ms, 102 * ms),
            Event("fusion.2", 102 * ms, 150 * ms),
            Event("all-reduce-done.1", 150 * ms, 180 * ms),
            Event("fusion.3", 180 * ms, 200 * ms),
        ]),
        Line("Async XLA Ops", [
            Event("all-reduce-start.1", 100 * ms, 180 * ms),
            Event("copy-start.7", 0 * ms, 90 * ms),
        ]),
    ])
    dev1 = Plane("/device:TPU:1", [
        Line(tr.OPS_LINE, [
            Event("fusion.1", 0 * ms, 100 * ms),
            Event("all-reduce.2", 100 * ms, 160 * ms),
            Event("fusion.3", 170 * ms, 200 * ms),
        ]),
    ])
    host = Plane(tr.HOST_PLANE, [Line("python3", [
        Event(tr.WINDOW_SPAN, 0 * ms, 200 * ms),
        Event("bench.run_call", 0 * ms, 165 * ms),
        Event("bench.sync", 165 * ms, 200 * ms),
        Event("other.thing", 0 * ms, 500 * ms),
    ])])
    return [dev0, dev1, host]


def test_exposed_against_hidden_collective_time():
    red = tr.reduce_trace(_two_devices(), steps=2)
    d0, d1 = red.devices
    # in flight 100..180; fusion.2 hides 102..150 of it. The `while` that
    # holds everything is no leaf and hides nothing.
    assert d0.collective_ns == pytest.approx(80e6)
    assert d0.collective_exposed_ns == pytest.approx(32e6)
    # a synchronous all-reduce is exposed from end to end
    assert d1.collective_ns == pytest.approx(60e6)
    assert d1.collective_exposed_ns == pytest.approx(60e6)
    assert red.median(lambda d: d.collective_exposed_ns) == pytest.approx(46e6)
    # every device by itself: busy 200 and 190 ms, the contract's busy_s
    # is their mean
    assert red.busy_s == pytest.approx(0.195)
    assert red.window_s == pytest.approx(0.2)
    assert d1.idle_share == pytest.approx(0.05)


def test_idle_gaps_go_to_what_the_host_was_doing():
    gaps = [(10.0, 20.0), (50.0, 60.0), (90.0, 95.0)]
    spans = [Event("bench.run_call", 0.0, 15.0), Event("bench.sync", 15.0, 55.0),
             Event("bench.inner", 16.0, 18.0)]
    got = tr.attribute_gaps(gaps, spans)
    assert got == pytest.approx({"bench.run_call": 5e-9, "bench.sync": 8e-9,
                                 "bench.inner": 2e-9, "(no span)": 10e-9})
    # device 1 of the synthetic trace idles 160..170, under the run call
    red = tr.reduce_trace(_two_devices()[1:], steps=2)
    assert red.top_gaps() == [["bench.run_call", pytest.approx(0.005)],
                              ["bench.sync", pytest.approx(0.005)]]


def test_no_device_plane_reads_as_nothing():
    host_only = _two_devices()[2:]
    assert tr.reduce_trace(host_only, steps=2) is None
    assert tr.reduce_trace(_two_devices()[:2], steps=2) is None  # no window


STEP_TEXT = '''
HloModule jit_fn

%fused_computation.166.clone (param_2.3652: bf16[32768,768], param_3.3251: bf16[32768,768], param_4.2861: bf16[1,768], param_5.2750: bf16[1,768]) -> (bf16[32768,768], f32[1,32768], f32[1,32768]) {
  %param_2.3652 = bf16[32768,768]{1,0:T(8,128)(2,1)} parameter(0)
  %param_3.3251 = bf16[32768,768]{1,0:T(8,128)(2,1)} parameter(1)
  %param_4.2861 = bf16[1,768]{1,0:T(2,128)(2,1)} parameter(2)
  %param_5.2750 = bf16[1,768]{1,0:T(2,128)(2,1)} parameter(3)
  ROOT %add_ln_fwd.23 = (bf16[32768,768]{1,0:T(8,128)(2,1)}, f32[1,32768]{1,0:T(1,128)}, f32[1,32768]{1,0:T(1,128)}) custom-call(%param_2.3652, %param_3.3251, %param_4.2861, %param_5.2750), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[32768,768]{1,0}, bf16[32768,768]{1,0}, bf16[1,768]{1,0}, bf16[1,768]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(fn)/jvp()/while/body/closed_call/add_ln_fwd/pallas_call" stack_frame_id=148}, backend_config={"custom_call_config": {"body": "TUzv"}}
}

ENTRY %main.1 (x: bf16[32768,768]) -> f32[8] {
  %x = bf16[32768,768]{1,0:T(8,128)(2,1)} parameter(0)
  %y = bf16[32768,768]{1,0:T(8,128)(2,1)S(1)} copy(%x)
  %scale = bf16[1,768]{1,0:T(2,128)(2,1)S(1)} constant({...})
  %stats = (f32[1,32768]{1,0:T(1,128)}, f32[1,32768]{1,0:T(1,128)}) custom-call(%x), custom_call_target="other"
  %mean = f32[1,32768]{1,0:T(1,128)} get-tuple-element(%stats), index=0
  %rstd = f32[1,32768]{1,0:T(1,128)} get-tuple-element(%stats), index=1
  %q = bf16[64,512,768]{2,1,0:T(8,128)(2,1)} bitcast(%x)
  %bias = f32[64,1,512]{2,1,0:T(1,128)} constant({...})
  %seed = s32[1]{0:T(128)S(1)} constant({7})
  %add_ln_fwd.25 = (bf16[32768,768]{1,0:T(8,128)(2,1)}, f32[1,32768]{1,0:T(1,128)}, f32[1,32768]{1,0:T(1,128)}) fusion(%x, %x, %scale, %scale), kind=kCustom, calls=%fused_computation.166.clone, metadata={op_name="jit(fn)/jvp()/while/body/closed_call/add_ln_fwd/pallas_call" stack_frame_id=148}
  %transpose_jvp_add_ln_bwd__.3 = (bf16[32768,768]{1,0:T(8,128)(2,1)}, f32[32,1,768]{2,1,0:T(1,128)S(1)}, f32[32,1,768]{2,1,0:T(1,128)S(1)}) custom-call(%x, %y, %scale, %mean, %rstd, /*index=5*/%x), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[32768,768]{1,0}, bf16[32768,768]{1,0}, bf16[1,768]{1,0}, f32[1,32768]{1,0}, f32[1,32768]{1,0}, bf16[32768,768]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(fn)/transpose(jvp(add_ln_bwd))/pallas_call" stack_frame_id=41}, backend_config={"flag_configs":[]}
  %flash_bsh_fwd.12 = (bf16[64,512,768]{2,1,0:T(8,128)(2,1)}, f32[64,12,512]{2,1,0:T(2,128)S(1)}) custom-call(%q, %q, %q, %bias, %seed), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[64,512,768]{2,1,0}, bf16[64,512,768]{2,1,0}, bf16[64,512,768]{2,1,0}, f32[64,1,512]{2,1,0}, s32[1]{0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(fn)/jvp()/while/body/closed_call/flash_bsh_fwd/pallas_call" stack_frame_id=110}, backend_config={"flag_configs":[]}
  ROOT %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(fn)/transpose(jvp())/while/body/closed_call/bsh,hk->bsk/dot_general" stack_frame_id=7}
}
'''


def test_mosaic_calls_are_read_from_the_compiled_text():
    step = hlo_text.read_step(STEP_TEXT)
    assert {n: c.kernel for n, c in step.calls.items()} == {
        "add_ln_fwd.23": "add_ln_fwd",
        # XLA fused the call with a neighbour: the trace shows the fusion
        "add_ln_fwd.25": "add_ln_fwd",
        "transpose_jvp_add_ln_bwd__.3": "add_ln_bwd",
        "flash_bsh_fwd.12": "flash_bsh_fwd"}
    assert step.calls["add_ln_fwd.25"] is step.calls["add_ln_fwd.23"]
    assert step.kernels == ["add_ln_bwd", "add_ln_fwd", "flash_bsh_fwd"]
    flash = step.calls["flash_bsh_fwd.12"]
    assert flash.operands[0] == hlo_text.Shape("bf16", (64, 512, 768), 0)
    assert flash.results[1] == hlo_text.Shape("f32", (64, 12, 512), 1)
    # the trace's event names lead back to the kernel, old style and new
    assert step.kernel_of(tr.instruction_name(
        "%flash_bsh_fwd.12 = (bf16[64")) == "flash_bsh_fwd"
    assert step.kernel_of(tr.instruction_name("add_ln_fwd.25")) == "add_ln_fwd"
    assert step.kernel_of("fusion.3") is None
    assert step.label("fusion.3") == "fusion.3 closed_call/bsh,hk->bsk/dot_general"
    assert step.label("add_ln_fwd.25") == "add_ln_fwd"
    assert step.label("copy.9") == "copy.9"


def test_kernel_work_against_hand_values():
    step = hlo_text.read_step(STEP_TEXT)
    fwd = step.calls["add_ln_fwd.23"]
    bwd = step.calls["transpose_jvp_add_ln_bwd__.3"]
    flash = step.calls["flash_bsh_fwd.12"]
    ln_fwd = manifest.load_module("kernels", "add_ln_fwd")
    ln_bwd = manifest.load_module("kernels", "add_ln_bwd")
    tensor = 32768 * 768 * 2  # one [R, H] bf16 tensor: 50.3 MB
    # with everything in HBM: x, y, out forward; x, y, g, dx backward
    assert fwd.bytes_moved == pytest.approx(3 * tensor, rel=0.01)
    assert bwd.bytes_moved == pytest.approx(4 * tensor, rel=0.01)
    assert (fwd.bytes_moved + bwd.bytes_moved) / 1e9 == pytest.approx(
        0.35, abs=0.005)
    assert ln_fwd.work(fwd) == (0.0, fwd.bytes_moved)
    # a buffer XLA placed on the chip (layout S(1)) costs no HBM traffic:
    # the backward's y and its partials here
    assert ln_bwd.work(bwd)[1] == pytest.approx(3 * tensor, rel=0.01)
    assert ln_bwd.work(bwd)[1] == bwd.hbm_bytes < bwd.bytes_moved
    assert (ln_fwd.BOUND, ln_bwd.BOUND) == ("hbm", "hbm")

    fl_fwd = manifest.load_module("kernels", "flash_bsh_fwd")
    fl_bwd = manifest.load_module("kernels", "flash_bsh_bwd")
    flops, nbytes = fl_fwd.work(flash)
    # 12 heads x 64 sequences x two [512,512]x64 products, 2 FLOPs a MAC
    assert flops == 12 * 64 * 2 * (2 * 512 * 512 * 64)
    assert fl_bwd.work(flash)[0] == 2.5 * flops
    assert nbytes == pytest.approx(4 * 64 * 512 * 768 * 2, rel=0.01)
    assert fl_fwd.BOUND == "compute"
    # at S = 512 the two bounds meet: 0.26 ms of matmul, 0.25 ms of HBM
    peaks = manifest.load_peaks("TPU v5 lite")
    assert flops / peaks["bf16_flops_per_s"] == pytest.approx(0.26e-3, abs=1e-5)
    assert nbytes / peaks["hbm_bytes_per_s"] == pytest.approx(0.25e-3, abs=1e-5)


def test_peaks_table_and_unknown_device():
    v5e = manifest.load_peaks("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"],
            v5e["hbm_bytes"], v5e["ici_bits_per_s"]) == (
        197e12, 819e9, 16e9, 1600e9)
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(KeyError, match="not in benchmark/peaks.json"):
            manifest.load_peaks(kind)
