"""The trace reduction on a small trace recorded on the CPU (committed as
a fixture: a jitted step whose generator is a nested ``jit(gen_fn)``,
three steps inside a ``chipbench_window`` host annotation), and on
hand-made intervals."""
import json
from pathlib import Path

import pytest

from chipbench import harness, trace

FIX = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def fixture_trace():
    names = json.loads((FIX / "cpu_step_hlo.json").read_text())
    return trace.load(str(FIX / "cpu_step.xplane.pb"), names)


def test_ops_and_spans_are_read(fixture_trace):
    ops, spans = fixture_trace
    assert {op.device for op in ops} == {"cpu:0"}
    assert any("jit(gen_fn)" in op.scope for op in ops)
    assert any("transpose(jvp())" in op.scope for op in ops)
    names = {s.name for s in spans}
    assert {"chipbench_window", "dispatch", "readback"} <= names


def test_summary_of_the_fixture(fixture_trace):
    ops, spans = fixture_trace
    s = trace.summarize(ops, spans, harness.TRACE_WINDOW,
                        harness.trace_groups(), label=harness.op_label)
    assert s.n_devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert 0 <= s.idle_pct < 100
    gen, model = s.groups_s["gen"], s.groups_s["model"]
    assert gen > 0 and model > 0 and gen + model <= s.busy_s * (1 + 1e-9)
    assert s.groups_s["collective"] == 0
    assert 0 < len(s.top_ops) <= 10
    secs = [t for _, t in s.top_ops]
    assert secs == sorted(secs, reverse=True)
    assert s.top_ops[0][0].startswith("sort") and "jit(gen_fn)" in \
        s.top_ops[0][0]
    assert sum(secs) <= s.busy_s * (1 + 1e-9)
    assert all(t > 0 for _, t in s.idle_gaps)
    with pytest.raises(ValueError, match="no host annotation"):
        trace.summarize(ops, spans, "no_such_window", {})


def test_union_self_time_and_gaps():
    assert trace.union_ns([(0, 10), (5, 12), (20, 25)]) == 17
    assert trace.union_ns([]) == 0
    ev = [(0, 10, "loop"), (1, 3, "a"), (4, 6, "b"), (12, 14, "c")]
    assert trace.self_times(ev) == [6, 2, 2, 2]
    assert trace._gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8),
                                                            (9, 10)]


def test_hlo_names_and_tpu_event_names():
    hlo = ('  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'calls=%fc, metadata={op_name="jit(step)/jit(gen_fn)/mul" '
           'source_file="x.py"}\n'
           '  ROOT all-to-all.3 = s32[4]{0} all-to-all(s32[4]{0} %a), '
           'metadata={op_name="jit(step)/jit(gen_fn)/all_to_all"}\n'
           '  %constant.1 = s32[] constant(0)\n')
    assert trace.hlo_op_names(hlo) == {
        "fusion.12": "jit(step)/jit(gen_fn)/mul",
        "all-to-all.3": "jit(step)/jit(gen_fn)/all_to_all"}
    m = trace._EVENT_NAME.match("%copy-start.1 = (f32[128,128]{1,0}) "
                                "copy-start(f32[128,128]{1,0} %w.1)")
    assert m.group(1) == "copy-start.1"
    op = trace.Op("d", "all-to-all.3", 0, 1, "", "")
    assert op.is_collective()
    assert not trace.Op("d", "fusion.1", 0, 1, "", "").is_collective()
