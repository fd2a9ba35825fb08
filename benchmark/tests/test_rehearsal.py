"""``run.py`` end to end on the CPU at a tiny preset that lives here only
(``preset/``): the control flow of a run, the control that breaks a
guarantee, and the timed path broken underneath.  Nothing here prints a
result line under a device's name: ``run_cell`` is called with the look
for a chip skipped and its result stays in the test."""

import json
import os
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
REAL = os.path.join(run.ROOT, "BENCHMARK.json")


def preset(tmp_path) -> str:
    """The real ``BENCHMARK.json`` with the preset's two cells in the
    place of its own: the same metrics, read by the same files.  A
    metric that lists cells lists the preset's cell with readers."""
    bench = json.load(open(REAL))
    cells = json.load(open(os.path.join(HERE, "preset", "cells.json")))
    bench.update(configs=cells["configs"], workloads=cells["workloads"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny_q7_backlog"]
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def rehearse(tmp_path, cell, seed=5, seconds=3.0, **kw):
    result, window = run.run_cell(
        cell, seed, seconds, False, bench_path=preset(tmp_path),
        require_tpu=False, out_root=str(tmp_path), **kw)
    assert result["device"]["platform"] == "cpu"
    return result, window


@pytest.mark.parametrize("cell", ["tiny_q7_backlog", "tiny_q5_backlog"])
def test_rehearsal_is_correct(tmp_path, cell):
    # a seed past 32 signed bits, as the driver's are
    result, window = rehearse(tmp_path, cell, seed=2**31 + 12345)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert list(result)[-1] == "checks"
    names = {m["name"] for m in window["cell"]["end_to_end"]}
    assert set(result["metrics"]) == names
    assert result["metrics"]["rows_per_s"]["value"] > 0
    assert result["checks"]["closed_windows"][0] >= 1
    # every barrier of the window left its epoch on disk, and the reader
    # of the checkpoint's size found them
    assert len(window["epochs_on_disk"]) >= window["barriers"] >= 2
    ckpt = run.load_module(run.reader_path(
        "per_layer", "ckpt_bytes_per_barrier")).read(window)
    assert ckpt > 0
    if window["cell"]["traffic"]["readers"]:
        assert len(window["reads"]) >= 8
    # the run leaves no data directory behind
    assert not os.path.exists(os.path.join(str(tmp_path), cell, "data"))


def test_control_rarer_checkpoint_is_not_correct(tmp_path):
    """The control: the configuration promises a checkpoint every
    barrier; ``checkpoint_frequency = 2`` is the step that would tempt a
    later PR (half the snapshots, half the uploads)."""
    result, _ = rehearse(tmp_path, "tiny_q7_backlog",
                         overrides={"checkpoint_frequency": 2})
    assert result["correct"] is False
    v, op, lim = result["checks"]["barriers_without_epoch_on_disk"]
    assert v >= 2 and (op, lim) == ("<=", 0)
    assert result["failed"] >= v
    # the program's own count agrees with the disk
    assert result["checks"]["barriers_uncommitted"][0] >= 2


def test_upload_acknowledged_but_not_written_is_not_correct(tmp_path,
                                                            monkeypatch):
    """The program says every upload was committed (its counter and its
    ``committed_epoch`` move); every other one wrote nothing.  Only the
    look at the disk sees it."""
    monkeypatch.setenv("BENCH_FAULT", "skip_write")
    result, _ = rehearse(
        tmp_path, "tiny_q7_backlog",
        child_script=os.path.join(HERE, "broken_child.py"))
    assert result["correct"] is False
    assert result["checks"]["barriers_without_epoch_on_disk"][0] >= 2
    assert result["checks"]["barriers_uncommitted"][0] == 0
    assert result["checks"]["view_rows_differ"][0] == 0


@pytest.mark.parametrize("fault,cell,number", [
    ("alter_answer", "tiny_q7_backlog", "view_rows_differ"),
    ("drop_half", "tiny_q7_backlog", "view_rows_differ"),
    ("drop_half", "tiny_q5_backlog", "view_rows_differ"),
    ("skip_step", "tiny_q7_backlog", "view_rows_differ"),
])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault,
                                          cell, number):
    monkeypatch.setenv("BENCH_FAULT", fault)
    result, window = rehearse(
        tmp_path, cell,
        child_script=os.path.join(HERE, "broken_child.py"))
    assert result["correct"] is False
    assert result["checks"][number][0] > 0
    if window["reads"]:
        assert result["checks"]["read_rows_differ"][0] > 0


def test_no_chip_no_result_line():
    """The command itself, where JAX finds no TPU: no result, not 0."""
    p = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE), "run.py"),
         "--workload", "q7_inner_agg_backlog", "--seed", "1",
         "--seconds", "1",
         "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr


def test_unknown_device_is_an_error():
    with pytest.raises(run.RunFailure):
        run.load_peaks("TPU v9 imaginary")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_names_files_that_exist():
    bench = json.load(open(REAL))
    for w in bench["workloads"]:
        cell = run.load_cell(REAL, w["name"])
        for kind in ("end_to_end", "per_layer"):
            assert len(cell[kind]) >= 2
            for m in cell[kind]:
                assert os.path.isfile(run.reader_path(kind, m["name"]))
        moved = {m["moves"] for m in cell["per_layer"]}
        assert moved <= {m["name"] for m in cell["end_to_end"]}
        assert cell["config"]["horizon_rows"] > 0
