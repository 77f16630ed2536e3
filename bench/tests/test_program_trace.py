"""The reduction of the program's own spans and device scopes
(`bench/lib/program_trace.py`).

Hand-made lines first; then a trace recorded on a TPU v5e by
`bench/tools/record_program_trace.py` (`data/tiny_program.xplane.pb`,
with the round program's compiled text `data/tiny_program.hlo.txt`):
two rounds of a reduced VGG split session and two joins and three
steps of a reduced phi4-mini split server.  Expected values were read
off the raw events (`ProfileData`), not through the reduction."""
import pathlib

import pytest

from bench.lib import program_trace as pt
from bench.lib import trace as tr

DATA = pathlib.Path(__file__).parent / "data"
TPU0 = "/device:TPU:0"


# ---------------------------------------------------------------------------
# hand-made lines
# ---------------------------------------------------------------------------

OPS = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50)]


def test_busy_between_matches_busy_ns():
    index = pt.busy_index(OPS)
    for lo, hi in [(0, 60), (12, 45), (25, 42), (30, 40), (0, 5),
                   (44, 46), (20, 21)]:
        assert pt.busy_between(index, lo, hi) == tr.busy_ns(OPS, lo, hi)


def test_idle_inside_host_intervals():
    index = pt.busy_index(OPS)
    # [0, 12) holds 10 idle; [28, 45) holds 10 (30-40); overlaps merge
    assert pt.idle_ns(index, [(0, 12), (28, 45), (35, 41)]) == 10 + 10
    assert pt.idle_ns(index, []) == 0


def test_launches_in_a_span():
    launches = [5, 10, 10, 20, 31]
    assert pt.launches_in(launches, 10, 20) == 2      # [lo, hi)
    assert pt.launches_in(launches, 0, 100) == 5
    assert pt.launches_in(launches, 21, 31) == 0


def test_self_time_leaves_out_children():
    spans = [("p", 0, 100), ("c1", 10, 30), ("g", 15, 40), ("c2", 50, 60),
             ("other", 90, 120)]
    # children and grandchildren as one union: [10, 40) and [50, 60)
    assert pt.self_ns(spans, spans[0]) == 100 - 30 - 10
    assert pt.self_ns(spans, spans[3]) == 10


def test_idle_by_innermost_span():
    spans = [("bench.step", 0, 60), ("repro.batcher.step", 5, 55),
             ("repro.batcher.tokens", 32, 45)]
    segs = pt.innermost(spans, 0, 70)
    assert segs == [(0, 5, "bench.step"), (5, 32, "repro.batcher.step"),
                    (32, 45, "repro.batcher.tokens"),
                    (45, 55, "repro.batcher.step"), (55, 60, "bench.step"),
                    (60, 70, "outside-spans")]
    idle = pt.idle_by_span(pt.busy_index(OPS), spans, 0, 70)
    # busy 10-30 and 40-50; the gap 30-40 splits where the host moved
    # into tokens at 32
    assert idle == {"bench.step": 5 + 5, "repro.batcher.step": 7 + 5,
                    "repro.batcher.tokens": 13 - 5, "outside-spans": 10}
    assert sum(idle.values()) + tr.busy_ns(OPS, 0, 70) == 70


@pytest.mark.parametrize("path,step", [
    ("jit(_round)/while/body/closed_call/ClientFwd/jvp()/conv", "ClientFwd"),
    ("jit(_round)/while/body/ClientBwd/transpose(jvp())/conv", "ClientBwd"),
    ("jit(f)/transpose(jvp(ClientFwd))/dot_general", "ClientBwd"),
    ("jit(f)/ClientBwd/transpose(ClientFwd)/pallas_call", "ClientBwd"),
    ("jit(f)/ServerFwdBwd/transpose(jvp(ServerFwdBwd))/mul", "ServerFwdBwd"),
    ("jit(_round)/while/body/closed_call/optimizer/sub", "optimizer"),
    ("jit(_round)/while/body/WeightHandoff/SendCut/x", "SendCut"),
    ("jit(_round)/while/body/dynamic_slice", None),
    ("jit(optimizers)/add", None),
])
def test_op_name_to_ir_step(path, step):
    assert pt.ir_step(path) == step


HLO = """HloModule jit__round, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.1 = f32[4]{0} negate(%p), metadata={op_name="jit(_round)/optimizer/neg"}
}

ENTRY %main.5 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.3 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_round)/ClientFwd/sin" source_file="m.py" source_line=3}
  ROOT %copy-start.2 = f32[4]{0} copy(%fusion.3)
}

HloModule jit_less

ENTRY %main (a: s32[]) -> pred[] {
  ROOT %lt = pred[] compare(%a, %a), direction=LT, metadata={op_name="jit(less)/lt"}
}
"""


def test_compiled_text_names_each_instruction():
    names = pt.hlo_op_names(HLO)
    assert names["jit__round"]["%fusion.3"] == "jit(_round)/ClientFwd/sin"
    assert names["jit__round"]["%neg.1"] == "jit(_round)/optimizer/neg"
    assert "%copy-start.2" not in names["jit__round"]      # no op_name
    assert names["jit_less"] == {"%lt": "jit(less)/lt"}


def test_a_fusion_holds_the_steps_fused_into_it():
    # the fusion's own op_name is its root's; the optimizer's negate
    # was fused into it
    held = pt.hlo_held_steps(HLO)
    assert held == {"jit__round": {"%fusion.3": {"ClientFwd", "optimizer"}},
                    "jit_less": {}}
    t = pt.ProgramTrace(ops={TPU0: [("%fusion.3", 20, 30), ("%x", 30, 35)]},
                        spans=[], shift_ns={TPU0: 0},
                        modules={TPU0: [("jit__round", 20, 40)]})
    ctx = {"trace": t, "planes": [t.ops[TPU0]], "lo": 0, "hi": 100,
           "hlo_text": HLO, "busy_s": 15e-9}
    assert pt.held_times(ctx) == {"ClientFwd": 10, "optimizer": 10}
    assert pt.step_times(ctx) == {"ClientFwd": 10, pt.UNMAPPED: 5}
    assert pt.step_share(ctx, "ClientFwd") == pytest.approx(100 * 10 / 15)


def test_ops_map_by_their_module():
    names = pt.hlo_op_names(HLO)
    modules = [("jit_less", 0, 10), ("jit__round", 20, 100)]
    ops = [("%lt", 2, 4), ("%fusion.3", 22, 40), ("%while.1", 40, 90),
           ("%fusion.3 = f32[4] custom-call(...)", 45, 50),
           ("%copy-start.2", 60, 70), ("%lt", 80, 85)]
    got = list(pt.mapped_ops(ops, modules, names, 0, 100))
    assert got == [("%lt", pt.UNMAPPED, 2), ("%fusion.3", "ClientFwd", 18),
                   ("%fusion.3", "ClientFwd", 5),
                   ("%copy-start.2", pt.UNMAPPED, 10),
                   ("%lt", pt.UNMAPPED, 5)]
    # clipped to the window
    assert [ns for *_, ns in pt.mapped_ops(ops, modules, names, 30, 65)] \
        == [10, 5, 5]


# ---------------------------------------------------------------------------
# the recorded chip trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prog():
    t = pt.load(DATA / "tiny_program.xplane.pb")
    lo, hi = tr.window(t.spans, "bench.window")
    ops = t.ops[TPU0]
    return {"trace": t, "lo": lo, "hi": hi, "planes": [ops],
            "busy_s": tr.busy_ns(ops, lo, hi) * 1e-9,
            "hlo_text": (DATA / "tiny_program.hlo.txt").read_text()}


def test_program_spans_launches_and_modules(prog):
    t = prog["trace"]
    names = [n for n, _, _ in t.spans]
    for name, count in [("repro.engine.run_round", 2),
                        ("repro.engine.host_read", 2),
                        ("repro.batcher.join", 2), ("repro.batcher.price", 2),
                        ("repro.batcher.step", 3),
                        ("repro.batcher.client", 6)]:
        assert names.count(name) == count, name
    # one launch per program execution, so the clock shift pairs them
    assert len(t.launches) == len(t.modules[TPU0]) == 96
    assert t.shift_ns[TPU0] == 353_325
    rounds = [m for m in t.modules[TPU0] if m[0] == "jit__round"]
    assert len(rounds) == 2


def test_idle_inside_program_spans(prog):
    # medians of the idle ns inside each span, read off the raw events
    assert pt.span_idle_ms(prog, "repro.engine.run_round") == \
        pytest.approx((2_401_060 + 1_735_460) / 2 * 1e-6)
    assert pt.span_idle_ms(prog, "repro.batcher.join") == \
        pytest.approx((57_354_805 + 122_032_888) / 2 * 1e-6)
    assert pt.span_idle_ms(prog, "repro.batcher.step") == \
        pytest.approx(12_049_055e-6)
    assert pt.span_launches(prog, "repro.batcher.step") == 26
    assert pt.idle_in_program(prog) == pytest.approx(
        100 * 219_641_150 / 221_400_709)
    assert pt.span_idle_ms(prog, "repro.no_such_span") is None


def test_idle_by_innermost_span_sums_to_the_idle_window(prog):
    t, lo, hi = prog["trace"], prog["lo"], prog["hi"]
    idle = pt.idle_by_span(pt.busy_index(prog["planes"][0]), t.spans, lo, hi)
    assert sum(idle.values()) == hi - lo - tr.busy_ns(prog["planes"][0],
                                                      lo, hi)
    # the per-join byte pricing (an eval_shape retrace) idles the most
    assert idle.most_common(1)[0][0] == "repro.batcher.price"


def test_round_ops_by_ir_step(prog):
    # ns in the two jit__round executions, each op's step read off its
    # op_name in the compiled text
    steps = pt.step_times(prog)
    assert {k: v for k, v in steps.items() if k != pt.UNMAPPED} == {
        "WeightHandoff": 16_666, "ClientFwd": 13_772, "SendCut": 2_655,
        "ServerFwdBwd": 27_570, "optimizer": 26_147, "RecvGrad": 2_001,
        "ClientBwd": 14_926}
    # the batcher's programs have no text here: all unmapped
    assert steps[pt.UNMAPPED] > 100_000
    # the costliest op of the round: a client convolution
    by_op = {i: st for i, st, _ in pt.mapped_ops(
        prog["planes"][0], prog["trace"].modules[TPU0],
        pt.hlo_op_names(prog["hlo_text"]), prog["lo"], prog["hi"])}
    assert by_op["%fusion.157"] == "ClientFwd"
    assert by_op["%fusion.166"] == "ClientBwd"


def test_load_adds_to_what_trace_load_keeps(prog):
    base = tr.load(DATA / "tiny_program.xplane.pb")
    t = prog["trace"]
    assert t.ops == base.ops and t.shift_ns == base.shift_ns
    assert [sp for sp in t.spans if sp[0].startswith("bench.")] == base.spans


def test_readers_find_nothing_without_the_program(prog):
    """A program without spans, scopes or compiled text (the benchmark's
    own trace of an older program) reads None everywhere."""
    base = tr.load(DATA / "tiny_program.xplane.pb")
    ctx = {"trace": base, "lo": prog["lo"], "hi": prog["hi"],
           "planes": [base.ops[TPU0]], "busy_s": prog["busy_s"]}
    assert pt.idle_in_program(ctx) is None
    assert pt.span_idle_ms(ctx, "repro.batcher.step") is None
    assert pt.span_launches(ctx, "repro.batcher.step") is None
    assert pt.step_times(ctx) is None
    assert pt.step_share(ctx, "optimizer") is None
