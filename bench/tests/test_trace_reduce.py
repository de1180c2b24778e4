"""trace_reduce on a small trace recorded on a TPU v5e: two CIM kernel
calls, a bf16 matmul and a paged-attention call, under host spans
``bench.loop`` / ``bench.stage`` / ``bench.loop`` (data/probe.xplane.pb).
Expected values are worked out by hand from the trace's events, in ns:

  op (device clock)                      start      duration
  convert_element_type                   42638865   1172
  broadcast                              42640038    516
  broadcast                              42640554   1028
  ccim_matmul_prepacked_pallas           42641584  53496
  copy-start / copy-done                 45846645 / 45846660   13 / 3330
  convolution_reduce_fusion              45849992  11820
  copy-start / copy-done                 46673297 / 46673303    5 / 302
  paged_attention_pallas                 46673605  17352
  (earlier: ccim_matmul_prepacked_skinny_pallas 41812062, 18337)

  host spans: bench.loop [42663656, 44516266], bench.stage
  [44525256, 46892666], bench.loop [46898566, 48449305]
"""
import os

import pytest

import trace_reduce as tr

PATH = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")
NS = 1e-9
WINDOW = (42_000_000 * NS, 47_000_000 * NS)


@pytest.fixture(scope="module")
def raw():
    return tr.load(PATH, align=False)


def test_op_names():
    assert tr.op_name("%ccim_matmul_prepacked_pallas.1 = s32[128,1024] "
                      "custom-call(...)") == "ccim_matmul_prepacked_pallas"
    assert tr.op_name("%copy-start = (bf16[1024,1024]) copy-start(...)") \
        == "copy-start"
    assert tr.op_name("%pad.2 = s8[32,768] pad(...)") == "pad"
    assert tr.op_name("%convolution_reduce_fusion = bf16[] fusion(...)") \
        == "convolution_reduce_fusion"


def test_busy_union(raw):
    # 1172 + 516 + 1028 + 53496 (touching or 1-2 ns apart), then
    # 13 + 3330 + 11820, then 5 + 302 + 17352
    assert tr.busy_seconds(raw, WINDOW) == pytest.approx(89_034 * NS, abs=1e-12)


def test_gap_attribution(raw):
    by = dict(tr.gaps_by_label(tr.idle_gaps(raw, WINDOW)))
    assert by["outside bench spans"] == pytest.approx(638_868 * NS, abs=1e-12)
    assert by["bench.loop"] == pytest.approx(3_151_565 * NS, abs=1e-12)
    assert by["bench.stage"] == pytest.approx(1_120_533 * NS, abs=1e-12)
    assert sum(by.values()) + tr.busy_seconds(raw, WINDOW) == pytest.approx(
        5e-3, abs=1e-12)


def test_kernel_matching(raw):
    cim = ("ccim_matmul_prepacked_pallas", "ccim_matmul_prepacked_skinny_pallas")
    secs, n = tr.device_time(raw, cim, WINDOW)
    assert (n, secs) == (1, pytest.approx(53_496 * NS, abs=1e-12))
    secs, n = tr.device_time(raw, cim, (41_000_000 * NS, WINDOW[1]))
    assert (n, secs) == (2, pytest.approx(71_833 * NS, abs=1e-12))
    secs, n = tr.device_time(raw, ("paged_attention_pallas",), WINDOW)
    assert (n, secs) == (1, pytest.approx(17_352 * NS, abs=1e-12))
    assert tr.top_ops(raw, WINDOW, k=1) == [
        ("ccim_matmul_prepacked_pallas", pytest.approx(53_496 * NS, abs=1e-12))]


def test_alignment_and_window(raw):
    aligned = tr.load(PATH)
    # a program starts after its host enqueue: runs 24 and 25 lag 1.166
    # and 1.190 ms behind their DoEnqueueProgram events on the device clock
    assert 1.0e-3 < aligned.shift_s < 1.3e-3
    a, b = raw.ops[0][0], aligned.ops[0][0]
    assert b[1] - a[1] == pytest.approx(aligned.shift_s)
    assert tr.span_window(raw, "bench.loop") == pytest.approx(
        (42_663_656 * NS, 48_449_305 * NS))
    assert tr.span_window(raw, "bench.batch") is None
