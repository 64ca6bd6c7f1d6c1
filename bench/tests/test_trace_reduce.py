"""The reduction from trace events to device busy time, program and
operation time and idle time by host activity."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    """A 1000 ns window: operations at 100-300, 250-400 (overlapping) and
    600-700 on one device; the host serves a step throughout and sleeps
    at 400-600."""
    return [
        ("host", "main", "bench.window", 0, 1000),
        ("host", "main", "bench.serve.step", 0, 1000),
        ("host", "main", "bench.sleep", 400, 200),
        ("device:0", "XLA Modules", "jit_forward_decode_step(12)", 100, 300),
        ("device:0", "XLA Modules", "jit_kv_scatter(3)", 600, 100),
        ("device:0", "XLA Ops", "%fusion.1 = f32[16,4096]{1,0} fusion(f32[16]"
         " %p), kind=kLoop", 100, 200),
        ("device:0", "XLA Ops", "%closed_call.8 = f32[16,32,128]{2,1,0:T(8,128)}"
         " custom-call(s32[16,256] %a, f32[2400,16,2048] %b), "
         'custom_call_target="tpu_custom_call"', 250, 150),
        ("device:0", "XLA Ops", "%copy.3 = f32[8,2400,16,2048]{3,2,1,0} "
         "copy(f32[8,2400,16,2048] %args_0_.1)", 600, 100),
        ("device:0", "XLA Ops", "%fusion.1 = f32[16,4096]{1,0} fusion(f32[16]"
         " %p), kind=kLoop", 1100, 50),                 # outside the window
    ]


def test_busy_idle_and_names():
    r = TR.reduce(synthetic())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)          # 100-400 and 600-700
    assert r["gaps"] == 3                                 # 0-100, 400-600, 700-1000
    assert r["idle_by_host"]["bench.sleep"] == pytest.approx(200e-9)
    assert r["idle_by_host"]["bench.serve.step"] == pytest.approx(400e-9)
    assert r["modules"]["jit_forward_decode_step"] == {"s": pytest.approx(300e-9), "n": 1}
    # operations are named by program, HLO name and opcode; the late one
    # is clipped out
    key = "jit_forward_decode_step|%fusion.1 fusion"
    assert r["ops"][key]["n"] == 1
    assert TR.seconds_of(r["ops"], [("jit_forward_decode_step|",
                                      " tpu_custom_call")]) \
        == pytest.approx(150e-9)
    assert TR.count_of(r["ops"], ["jit_kv_scatter|%copy.3 copy"]) == 1
    b = TR.breakdown(r)
    assert b["device_ops"][0][0] == key
    assert b["idle_gaps"][0][0] == "bench.serve.step"


def test_no_window_or_no_device_gives_nothing():
    evs = synthetic()
    assert TR.reduce([e for e in evs if e[2] != "bench.window"]) is None
    assert TR.reduce([e for e in evs if e[0] == "host"]) is None


def test_operation_clipped_at_the_window_edges():
    evs = [("host", "m", "bench.window", 100, 100),
           ("device:0", "XLA Modules", "jit_f(1)", 40, 300),
           ("device:0", "XLA Ops", "a", 50, 100),
           ("device:0", "XLA Ops", "b", 180, 100)]
    r = TR.reduce(evs)
    assert r["busy_s"] == pytest.approx(70e-9)
    # the program began before the window: its operations are still its own
    assert r["ops"]["jit_f|a"]["s"] == pytest.approx(50e-9)
    assert r["modules"]["jit_f"]["s"] == pytest.approx(100e-9)


def test_recorded_chip_trace():
    """100 ms of a traced granite-l8.ragged_open window, recorded on a TPU
    v5 lite: two fused decode steps, each with 8 paged attention calls."""
    with gzip.open(DATA / "granite_decode_100ms.json.gz", "rt") as f:
        rec = json.load(f)
    r = TR.reduce([tuple(e) for e in rec["events"]], tuple(rec["window"]))
    assert r["window_s"] == pytest.approx(0.1)
    assert 0.09 < r["busy_s"] < r["window_s"]
    assert r["modules"]["jit_forward_decode_step"]["n"] == 2
    kernel = [("jit_forward_decode_step|", " tpu_custom_call")]
    attn = TR.seconds_of(r["ops"], kernel)
    assert TR.count_of(r["ops"], kernel) == 8
    assert 0.03 < attn < r["modules"]["jit_forward_decode_step"]["s"]
    # the run that began before the recording cannot be named
    assert any(k.startswith("?|") for k in r["ops"])
    assert sum(r["idle_by_host"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
