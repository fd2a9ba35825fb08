"""``trace_reduce.reduce_events`` on a small trace recorded on the chip
(``recorded_trace.json``: a traced part of a ``q7_backlog`` window on a
TPU v5e, PR 25 — every program run, every host phase, and the device
operations of 0.2 ms and longer) and on events written by hand."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
CFG = {"window_program": "jit__multi", "host_phases": []}


def test_names_are_shortened():
    assert tr.short_name("%while.93 = (u32[]{:T(128)}) while(...)") \
        == "%while.93"
    assert tr.short_name("$engine.py:2167 tick") == "engine.py tick"
    assert tr.module_name("jit__multi(4579428264187360047)") == "jit__multi"


def test_merge_is_a_union():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_idle_and_gap_causes_by_hand():
    events = [
        [DEV, tr.MODULE_LINE, "jit__multi(1)", 100, 400],
        [DEV, tr.OP_LINE, "%while.1", 100, 390],
        [DEV, tr.MODULE_LINE, "jit__barrier_impl(2)", 700, 100],
        [DEV, tr.MODULE_LINE, "jit__multi(1)", 900, 50],
        ["/host:CPU", "python", "runtime.py inject_barrier", 480, 400],
        ["/host:CPU", "python", "engine.py tick", 0, 1000],
        [tr.SPAN, tr.SPAN, tr.SPAN, 0, 1000],
    ]
    r = tr.reduce_events(events, CFG)
    assert r["busy_s"] == pytest.approx(550e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["window_program"]["runs"] == 2
    assert r["window_program"]["device_s"] == pytest.approx(450e-9)
    gaps = dict(r["idle_gaps"])
    # 500..700 and 800..900 fall inside inject_barrier (the innermost
    # frame recorded there), 0..100 and 950..1000 only inside tick
    assert gaps["host: runtime.py inject_barrier"] == pytest.approx(300e-9)
    assert gaps["host: engine.py tick"] == pytest.approx(150e-9)
    assert r["device_ops"][0] == ["program jit__multi",
                                  pytest.approx(450e-9)]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events([["/host:CPU", "python", "x", 0, 10]], CFG)


def test_unknown_window_program_reads_nothing():
    r = tr.reduce_events([[DEV, tr.MODULE_LINE, "jit_other(1)", 0, 10]],
                         CFG)
    assert r["window_program"]["runs"] == 0


def test_recorded_trace():
    rec = json.load(open(os.path.join(HERE, "recorded_trace.json")))
    r = tr.reduce_events(rec["events"], rec["cfg"])
    whole = rec["reduced_all"]  # the same reduction over every event
    # leaving out the operations under 0.2 ms changes neither the
    # programs' time nor, by more than a hundredth, the busy time
    assert r["window_program"] == whole["window_program"]
    assert r["window_program"]["runs"] >= 2
    assert r["window_s"] == pytest.approx(whole["window_s"])
    assert r["busy_s"] == pytest.approx(whole["busy_s"], rel=0.01)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] == "program jit__multi"
    assert any(k.startswith("host: checkpoint_store.py")
               for k, _ in r["idle_gaps"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
