"""The trace reduction on a trace recorded on a TPU v5e: a jitted step
of the int8 wire quantize and dequantize kernels and a small matmul,
run four times with a 2 ms host sleep between, inside a `bench.window`
span (`data/tiny.xplane.pb`, 25 KB).  Expected values were read off
the raw events by hand."""
import pathlib

import pytest

from bench.lib import counts, trace as tr, wire_kernels

DATA = pathlib.Path(__file__).parent / "data" / "tiny.xplane.pb"
TPU0 = "/device:TPU:0"


@pytest.fixture(scope="module")
def t():
    return tr.load(DATA)


def test_planes_and_spans(t):
    assert list(t.ops) == [TPU0]
    assert len(t.ops[TPU0]) == 20          # 5 ops x 4 steps
    names = [n for n, _, _ in t.spans]
    assert names.count("bench.step") == 4
    assert names.count("bench.sleep") == 4
    assert names.count("bench.window") == 1


def test_device_clock_moves_onto_the_host_clock(t):
    # the second execution starts 1,181,848 ns before its host launch on
    # the device's own clock: the largest such lead
    assert t.shift_ns[TPU0] == 1_181_848
    lo, hi = tr.window(t.spans, "bench.window")
    assert all(lo <= s and e <= hi for _, s, e in t.ops[TPU0])


def test_busy_time_is_the_union_of_ops(t):
    lo, hi = tr.window(t.spans, "bench.window")
    # the XLA Ops line's events do not overlap: busy is their sum
    assert tr.busy_ns(t.ops[TPU0], lo, hi) == 697_986
    assert tr.busy_ns([("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]) == 30
    assert tr.busy_ns([("a", 0, 10), ("b", 5, 20)], 8, 12) == 4


def _by_prefix(by, prefix):
    (value,) = [v for k, v in by.items() if k.startswith(prefix)]
    return value


def test_kernel_time_by_name(t):
    by = tr.time_by_name(t.ops[TPU0])
    assert _by_prefix(by, "%_wire_quant_jit.1 = (s8[65536,64]") == \
        100_224 + 100_125 + 100_056 + 100_090
    assert _by_prefix(by, "%_wire_dequant_jit.1 = f32[65536,64]") == \
        34_842 + 34_843 + 34_842 + 34_843
    assert tr.time_by_name([("%while.3", 0, 100), ("%fusion", 10, 20)]) \
        == {"%fusion": 10}


def test_wire_kernels_by_signature(t):
    lo, hi = tr.window(t.spans, "bench.window")
    ctx = {"planes": [t.ops[TPU0]], "lo": lo, "hi": hi,
           "peak": counts.peaks("TPU v5 lite")}
    quant = wire_kernels.calls(ctx, "wire_quant")
    dequant = wire_kernels.calls(ctx, "wire_dequant")
    # (64, 32, 32, 64) f32 as (65536, 64): 21,233,664 B a call
    assert [b for _, b in quant] == [21_233_664] * 4
    assert [b for _, b in dequant] == [21_233_664] * 4
    least = 4 * 21_233_664 / 819e9
    assert wire_kernels.roofline(ctx, "wire_quant") == pytest.approx(
        100 * least / 400_495e-9)
    assert wire_kernels.roofline(ctx, "wire_dequant") == pytest.approx(
        100 * least / 139_370e-9)


def test_idle_gaps_are_named_by_the_host_span(t):
    lo, hi = tr.window(t.spans, "bench.window")
    top = tr.attributed_gaps(t.ops[TPU0], t.spans, lo, hi, top=4)
    # the four longest gaps fall while the host sleeps between steps
    assert [name for name, _ in top] == ["bench.sleep"] * 4
    # between the steps' device work (ends and starts read off the ops)
    assert [g for _, g in top] == pytest.approx(
        [3_147_861e-9, 3_126_894e-9, 2_950_464e-9, 2_873_733e-9])
    gaps = tr.gaps(t.ops[TPU0], lo, hi)
    assert sum(e - s for s, e in gaps) + 697_986 == hi - lo


def test_gaps_of_a_hand_made_line():
    ops = [("a", 10, 20), ("b", 25, 30)]
    assert tr.gaps(ops, 0, 40) == [(0, 10), (20, 25), (30, 40)]
    spans = [("bench.window", 0, 40), ("bench.fence", 18, 26)]
    assert tr.attributed_gaps(ops, spans, 0, 40, top=2) == [
        ["bench.window", 10e-9], ["bench.window", 10e-9]]
    assert tr.span_at(spans, 22) == "bench.fence"
