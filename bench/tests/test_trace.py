"""The trace reduction, on events recorded from a TPU v5e trace (one
scale-10 job of each cell, traced by `run.py --trace 1`'s own path) and on
a hand-made trace whose numbers are known."""
from __future__ import annotations

import gzip
import json
import os
import types

import pytest

from helpers import BENCH, run  # noqa: F401  (puts bench/ on sys.path)
import devtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = ["ga500-16.pagerank", "g500-16.bfs"]


def _recorded(workload):
    with gzip.open(os.path.join(DATA, f"{workload}.scale10.events.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_kernel_names_from_chip_instructions():
    tri = ("%fused_triplet.1 = (f32[4096,1]{1,0:T(8,128)S(1)}, f32[4096,1]) "
           "custom-call(s32[56]{0:T(128)S(1)} %copy-done.1), "
           'custom_call_target="tpu_custom_call"')
    app = ("%fused_apply.12 = (f32[2048,3]{1,0}, f32[2048,1]{1,0}) "
           "custom-call(s32[16]{0} %reshape.92)")
    fus = "%fusion.4 = pred[8192]{0} fusion(pred[1921]{0} %pad_add_fusion.7)"
    assert devtrace.kernel_of(tri) == "triplet"
    assert devtrace.kernel_of(app) == "apply"
    assert devtrace.kernel_of(fus) is None
    assert devtrace.instruction(fus) == "fusion"
    assert devtrace.module_name("jit__unknown(9269020766353316105)") == \
        "jit__unknown"


def test_hand_made_trace():
    ev = {"devices": 1,
          "ops": [[0, "m:a", None, 10, 10], [0, "m:k", "triplet", 15, 12],
                  [0, "m:b", None, 40, 10], [0, "m:late", None, 70, 5]],
          "jobs": [[0, 30], [35, 60]]}
    red = devtrace.reduce(ev, compile_spans_ns=[(0, 5)])
    assert red["window_s"] == pytest.approx(60e-9)
    assert red["busy_s"] == pytest.approx(27e-9)          # [10,27] + [40,50]
    assert red["kernel_s"] == {"triplet": pytest.approx(12e-9)}
    assert red["other_s"] == pytest.approx(20e-9)
    gaps = red["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == [
        "inside a job call, not compiling",                # [27, 40]
        "compile: trace, lower, compile or cache load",    # [0, 10]
        "inside a job call, not compiling"]                # [50, 60]
    assert [g[1] for g in gaps] == pytest.approx([13e-9, 10e-9, 10e-9])
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert names[0] == "triplet kernel (kernels/triplet.py)"
    assert "m:late" not in names                           # after the window


@pytest.mark.parametrize("workload", RECORDED)
def test_recorded_chip_trace(workload):
    ev = _recorded(workload)
    red = devtrace.reduce(ev)
    assert ev["devices"] == 1 and len(ev["jobs"]) == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert set(red["kernel_s"]) == {"triplet", "apply"}
    device_time = sum(red["kernel_s"].values()) + red["other_s"]
    assert device_time >= red["busy_s"] - 1e-9
    idle = sum(red["idle_by_why"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    for key in ("device_ops", "idle_gaps"):
        assert 1 <= len(red["breakdown"][key]) <= 10
    names = [n for n, _ in red["breakdown"]["device_ops"]]
    assert "triplet kernel (kernels/triplet.py)" in names


@pytest.mark.parametrize("workload", RECORDED)
def test_layer_readers_on_recorded_trace(workload):
    red = devtrace.reduce(_recorded(workload))
    peaks = run.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = run.Cell(spec, workload)
    ctx = types.SimpleNamespace(
        window_s=red["window_s"], busy_s=red["busy_s"],
        kernel_s=red["kernel_s"], other_s=red["other_s"],
        host_window_s=red["window_s"], compile_s=0.25 * red["window_s"],
        supersteps=10, jobs=1, least_bytes=10**6, peaks=peaks)
    values = {name: reader.read(ctx)
              for name, (m, reader) in cell.per_layer.items()}
    assert values["driver.compile_share"] == pytest.approx(25.0)
    assert 0 < values["device.idle_share"] < 100
    assert values["triplet.ms_per_superstep"] == pytest.approx(
        1e3 * red["kernel_s"]["triplet"] / 10)
    assert 0 < values["triplet_roofline"] <= 100
    assert all(v is not None for v in values.values())
    # a kernel the trace does not hold is left out, never read as 0
    ctx.kernel_s = {}
    assert cell.per_layer["triplet_roofline"][1].read(ctx) is None
    assert cell.per_layer["apply.ms_per_superstep"][1].read(ctx) is None
