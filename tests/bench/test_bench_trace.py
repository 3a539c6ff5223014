"""The trace reduction: busy union, idle share, idle gaps by host span,
kernel sums by class, program runs; on a hand-made trace with known
answers and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

import _paths  # noqa: F401
from bench import trace as T

HLO = """HloModule jit_run, entry_computation_layout={}
  %bfp_conv2d_pallas.3 = f32[2,8]{1,0} custom-call(%p.0, %p.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/jit(bfp_conv2d_pallas)/pallas_call" stack_frame_id=1}
  %bfp_matmul_prequant_pallas.4 = f32[2,8]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/jit(bfp_matmul_prequant_pallas)/pallas_call"}
  %fusion.1 = f32[2,8]{1,0} fusion(%x), kind=kLoop
"""

# window 0-100; device ops (ns): conv 10-30 and 25-40 (overlap), fusion
# 50-60, matmul 70-75, and one op outside the window at 120
TRACE = {
    "devices": {"/device:TPU:0": {
        "ops": [["bfp_conv2d_pallas.3", 10, 20], ["bfp_conv2d_pallas.3", 25, 15],
                ["fusion.1", 50, 10], ["bfp_matmul_prequant_pallas.4", 70, 5],
                ["fusion.1", 120, 5]],
        "modules": [["jit_run(123)", 10, 65], ["jit_stack(9)", 80, 2],
                    ["jit_run(123)", 120, 5]]}},
    "host": [["bench.window", 0, 100], ["bench.submit", 0, 5],
             ["bench.step", 5, 70], ["bench.wait_arrival", 75, 25]],
}


def test_merge():
    assert T.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_instr_name():
    assert T.instr_name("%fusion.12 = f32[2]{0} fusion(%a)") == "fusion.12"
    assert T.instr_name("plain") == "plain"


def test_kernel_classes():
    c = T.kernel_classes([HLO])
    assert c == {"kernels": {"bfp_conv2d_pallas.3": "conv",
                             "bfp_matmul_prequant_pallas.4": "matmul"},
                 "programs": ["jit_run"]}


def test_summarize_by_hand():
    s = T.summarize(TRACE, T.kernel_classes([HLO]))
    assert s.window_s == pytest.approx(100e-9)
    # busy: [10, 40] + [50, 60] + [70, 75] = 45 ns
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share == pytest.approx(0.55)
    assert s.class_totals["conv"] == pytest.approx(35e-9)
    assert s.class_totals["matmul"] == pytest.approx(5e-9)
    assert s.op_totals["fusion.1"] == pytest.approx(10e-9)
    assert s.program_runs == [pytest.approx(65e-9)]
    # gaps: 0-10 (submit 5, step 5: the later span wins the tie) in
    # step, 40-50 and 60-70 inside the program run 10-75, 75-100 in
    # wait_arrival
    assert s.gaps["bench.step"][:2] == [pytest.approx(10e-9), 1]
    assert s.gaps["in-program"][:2] == [pytest.approx(20e-9), 2]
    assert s.gaps["bench.wait_arrival"][:2] == [pytest.approx(25e-9), 1]
    b = s.breakdown()
    assert b["device_ops"][0] == ["bfp_conv2d_pallas.3", pytest.approx(35e-9)]
    assert b["idle_gaps"][0][0].startswith("bench.wait_arrival: 1 gaps")


def test_recorded_chip_trace():
    with open(Path(__file__).with_name("vgg16_batch_trace.json")) as f:
        rec = json.load(f)
    s = T.summarize(rec["trace"], rec["classes"])
    ops = rec["trace"]["devices"]["/device:TPU:0"]["ops"]
    # 4 forwards of bucket 32 in the window, 16 kernels each: 13 conv
    # and 3 matmul
    assert len(s.program_runs) == 4
    assert all(0.1 < r < 0.12 for r in s.program_runs)
    conv = sum(d for n, _, d in ops if n in rec["classes"]["kernels"]
               and rec["classes"]["kernels"][n] == "conv") * 1e-9
    assert s.class_totals["conv"] == pytest.approx(conv)
    assert s.class_totals["conv"] > 0.8 * sum(s.program_runs)
    assert 0 < s.busy_s < s.window_s and 0 < s.idle_share < 1
    idle = sum(t for t, _, _ in s.gaps.values())
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-9)


def test_no_device_reads_nothing():
    s = T.summarize({"devices": {}, "host": [["bench.window", 0, 10]]},
                    T.kernel_classes([]))
    assert s.idle_share is None and s.busy_s == 0.0


def test_align_moves_host_spans_onto_the_trace_clock():
    # host clock = trace clock + 1000, and each step's logits reach the
    # host 2 ns after its forward's run ends
    host = [["bench.window", 1000, 100], ["bench.submit", 1000, 5],
            ["bench.step", 1005, 72], ["bench.wait_arrival", 1077, 23]]
    trace = {"devices": TRACE["devices"]}
    offset = T.align(trace, T.kernel_classes([HLO]), host)
    assert offset == -1002
    assert trace["host"][0] == ["bench.window", -2, 100]
    s = T.summarize(trace, T.kernel_classes([HLO]))
    assert s.window_s == pytest.approx(100e-9)
    assert s.program_runs == [pytest.approx(65e-9)]


def test_align_without_a_forward():
    trace = {"devices": {}}
    assert T.align(trace, T.kernel_classes([HLO]),
                   [["bench.step", 0, 5]]) is None
    assert trace["host"] == []


def test_host_spans_time_each_block():
    spans = T.HostSpans()
    with spans.span("bench.window"):
        with spans.span("bench.step"):
            pass
    assert [s[0] for s in spans.spans] == ["bench.step", "bench.window"]
    assert spans.spans[1][2] >= spans.spans[0][2] >= 0
