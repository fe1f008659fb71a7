"""trace_reduce on planes built by hand, and on a cut of one real v5e trace
(fixtures/v5e_trace_cut.json: two rounds of resnet9_sketch_w128, PR 24)."""

import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000  # ns


def planes(ops, modules=(), host=()):
    return [("/host:CPU", [("python", list(host))]),
            ("/device:TPU:0", [("XLA Ops", list(ops)), ("XLA Modules", list(modules))])]


def test_busy_union_window_and_idle():
    red = trace_reduce.reduce_planes(planes(
        ops=[("a", 0, 10 * US), ("b", 5 * US, 10 * US), ("c", 30 * US, 10 * US)]))
    assert red.chips == 1
    assert red.window_s == pytest.approx(40e-6)
    assert red.busy_s == pytest.approx(25e-6)  # [0,15] and [30,40]
    assert red.idle_share() == pytest.approx(15 / 40)


def test_self_time_takes_children_out():
    red = trace_reduce.reduce_planes(planes(
        ops=[("while", 0, 100 * US), ("fusion.1", 10 * US, 20 * US),
             ("fusion.1", 40 * US, 20 * US), ("kernel_accumulate", 70 * US, 10 * US)]))
    assert red.op_self_s["while"] == pytest.approx(50e-6)
    assert red.op_self_s["fusion.1"] == pytest.approx(40e-6)
    assert red.op_total_s["while"] == pytest.approx(100e-6)
    assert red.op_count["fusion.1"] == 2
    assert red.busy_s == pytest.approx(100e-6)
    assert red.ops_matching("accumulate") == (pytest.approx(10e-6), 1)
    assert red.ops_matching("no_such_kernel") == (0, 0)


def test_gaps_are_named_by_the_host():
    red = trace_reduce.reduce_planes(planes(
        ops=[("a", 0, 10 * US), ("b", 50 * US, 10 * US), ("c", 65 * US, 5 * US)],
        modules=[("jit_step", 0, 10 * US), ("jit_step", 50 * US, 20 * US), ("jit_other", 0, US)],
        host=[("run_loop", 0, 70 * US), ("device_get", 12 * US, 30 * US), ("tiny", 61 * US, US)]))
    assert red.gaps[0] == ("device_get", pytest.approx(40e-6))
    assert red.gaps[1][1] == pytest.approx(5e-6)
    assert red.main_module() == "jit_step" and len(red.modules["jit_step"]) == 2


def test_two_chips_are_averaged():
    two = planes(ops=[("a", 0, 10 * US)]) + [
        ("/device:TPU:1", [("XLA Ops", [("a", 0, 30 * US)])])]
    red = trace_reduce.reduce_planes(two)
    assert red.chips == 2 and red.busy_s == pytest.approx(20e-6)


def test_hlo_text_is_split_into_name_and_kind():
    text = ("%sort.1 = (f32[6573130]{0:T(1024)}, s32[6573130]{0:T(1024)}) "
            "sort(f32[6573130]{0:T(1024)S(1)} %get-tuple-element.417, s32[6573130]{0} %iota), "
            "dimensions={0}")
    assert trace_reduce._split_hlo(text) == ("%sort.1", "sort (f32[6573130], s32[6573130])")
    assert trace_reduce._split_hlo("plain name") == ("plain name", "")
    red = trace_reduce.reduce_planes(planes(ops=[
        ("%_accumulate_call.1 = f32[5,8]{1,0} custom-call(f32[8]{0} %v)", 0, 10 * US),
        ("%user = f32[] fusion(f32[5,8]{1,0} %_accumulate_call.1)", 10 * US, 5 * US)]))
    # an operation that only reads the kernel's result is not the kernel
    assert red.ops_matching("accumulate") == (pytest.approx(10e-6), 1)
    assert red.label("%user") == "%user fusion f32[]"


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([("/host:CPU", [("python", [("x", 0, 1)])])])


def test_recorded_v5e_trace():
    path = os.path.join(HERE, "fixtures", "v5e_trace_cut.json")
    with open(path) as f:
        cut = json.load(f)
    red = trace_reduce.reduce_planes(cut)
    assert red.chips == 1 and 0 < red.busy_s <= red.window_s
    main = red.main_module()
    assert main is not None and len(red.modules[main]) == 2
    # both Pallas kernels are in the round, once each a round
    for word in ("accumulate", "query"):
        secs, runs = red.ops_matching(word)
        assert runs == 2 and secs > 0
    assert 0 <= red.idle_share() < 0.5
    seen = trace_reduce.describe(cut)
    assert seen["/device:TPU:0"]["XLA Modules"]["events"] == 8
    assert "/host:CPU" in seen
