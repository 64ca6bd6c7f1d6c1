"""The reduction of the serving program's own spans (``pd.*``) beside the
benchmark's trace reduction: the program's events change nothing that
``trace_reduce.reduce`` reads, idle time goes to the innermost program
span, span time is a union, and the spans reach the profiler's trace."""
import tempfile
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import program_trace as PT
from bench import trace_reduce as TR


def synthetic():
    """A 1000 ns window on one device: operations at 100-300 (the decode
    step) and 600-700 (a prefill); idle 0-100, 300-600 and 700-1000. The
    benchmark serves a step all through and sleeps at 400-600; the
    program steps at 50-350 (reading back at 280-340), pumps the
    transfer at 350-550 with a scatter at 380-500 inside, and runs a
    prefill batch at 590-800."""
    return [
        ("host", "main", "bench.window", 0, 1000),
        ("host", "main", "bench.serve.step", 0, 1000),
        ("host", "main", "bench.sleep", 400, 200),
        ("device:0", "XLA Modules", "jit_forward_decode_step(12)", 100, 200),
        ("device:0", "XLA Modules", "jit_forward_prefill(3)", 600, 100),
        ("device:0", "XLA Ops", "%fusion.1 = f32[16]{0} fusion(f32[16] %p)",
         100, 200),
        ("device:0", "XLA Ops", "%fusion.2 = f32[16]{0} fusion(f32[16] %p)",
         600, 100),
    ]


def program():
    return [
        ("prog", "main", "pd.decode.step", 50, 300),
        ("prog", "main", "pd.decode.readback", 280, 60),
        ("prog", "main", "pd.xfer.pump", 350, 200),
        ("prog", "main", "pd.xfer.scatter", 380, 120),
        ("prog", "main", "pd.prefill.batch", 590, 210),
    ]


def test_program_events_change_nothing_the_benchmark_reads():
    base = TR.reduce(synthetic())
    both = TR.reduce(synthetic() + program())
    for key in ("busy_s", "modules", "ops", "idle_by_host", "gaps",
                "window_s"):
        assert both[key] == base[key], key


def test_idle_goes_to_the_innermost_program_span():
    r = PT.reduce_program(synthetic() + program())
    idle = r["idle_by_program"]
    # 0-100: mid 50 is the step's first instant; 300-600: mid 450 lies in
    # the scatter inside the pump; 700-1000: mid 850 lies in no span
    assert idle["pd.decode.step"] == pytest.approx(100e-9)
    assert idle["pd.xfer.scatter"] == pytest.approx(300e-9)
    assert idle[PT.NONE] == pytest.approx(300e-9)
    assert "pd.xfer.pump" not in idle
    assert sum(idle.values()) == pytest.approx(
        1e-6 - TR.reduce(synthetic())["busy_s"])
    # every gap lies in bench.serve.step but the 400-600 sleep's: the
    # 300-600 gap's middle (450) is the sleep's, so it is no serve idle
    assert r["named_idle_share"] == pytest.approx(100 / 400)


def test_span_time_is_a_union():
    evs = synthetic() + program() + [
        # the same name on a second thread, overlapping the first
        ("prog", "other", "pd.decode.step", 300, 100)]
    spans = PT.reduce_program(evs)["prog_spans"]
    assert spans["pd.decode.step"] == {"s": pytest.approx(350e-9), "n": 2}
    # the group's union: the scatter lies inside the pump
    assert spans["pd.xfer.*"] == {"s": pytest.approx(200e-9), "n": 2}
    # clipped to the window
    late = [("prog", "main", "pd.gateway.place", 900, 400)]
    spans = PT.reduce_program(synthetic() + late)["prog_spans"]
    assert spans["pd.gateway.place"]["s"] == pytest.approx(100e-9)


def test_program_runs_inside_their_spans():
    r = PT.reduce_program(synthetic() + program())
    inside = {"runs": 1, "share": 1.0, "share_raw": 1.0}
    assert r["inside"]["jit_forward_decode_step"] == inside
    assert r["inside"]["jit_forward_prefill"] == inside
    # the run may end 40 ns later or start 50 ns sooner: zero fits
    assert r["clock_offset_ms"] == {"lo": pytest.approx(-40e-6),
                                    "hi": pytest.approx(50e-6), "used": 0.0}
    # a step that opens after its program starts: the bounds cross
    late = [e if e[2] != "pd.decode.step" else e[:3] + (150, 200)
            for e in program()]
    r = PT.reduce_program(synthetic() + late)
    assert r["clock_offset_ms"]["hi"] < r["clock_offset_ms"]["lo"]
    assert r["inside"]["jit_forward_decode_step"]["share"] == 0.0


def test_device_timeline_moved_back_by_the_offset_the_steps_force():
    """The device events 90 ns late: the decode run ends 50 ns after its
    read-back, so the timeline moves back 50 ns, as little as it must."""
    evs = [e if not e[0].startswith("device:")
           else e[:3] + (e[3] + 90, e[4]) for e in synthetic()]
    r = PT.reduce_program(evs + program())
    assert r["clock_offset_ms"]["lo"] == pytest.approx(50e-6)
    assert r["clock_offset_ms"]["used"] == pytest.approx(50e-6)
    dec = r["inside"]["jit_forward_decode_step"]
    assert dec["share_raw"] == 0.0 and dec["share"] == 1.0
    assert r["inside"]["jit_forward_prefill"]["share"] == 1.0
    # the idle gaps move with it: 0-140, 340-640, 740-1000
    assert sum(r["idle_by_program"].values()) == pytest.approx(700e-9)
    assert r["idle_by_program"]["pd.decode.step"] == pytest.approx(140e-9)
    assert r["idle_by_program"]["pd.xfer.scatter"] == pytest.approx(300e-9)


def test_metrics_from_spans_counters_and_stamps():
    red = TR.reduce(synthetic())
    prog = PT.reduce_program(synthetic() + program())
    counters = {"handed_to_decode": 2.0, "xfer_builds": 16.0,
                "decode_steps": 1.0}
    m = PT.metrics(red, prog, counters, [3.0, 5.0, 40.0])
    assert m["kv_handoff_host_ms_per_req"] == pytest.approx(1e3 * 200e-9 / 2)
    assert m["kv_handoff_builds_per_req"] == 8.0
    assert m["handoff_wait_ms_p50"] == 5.0
    assert m["decode_host_ms_per_step"] == pytest.approx(1e3 * 100e-9)
    tracks = [SimpleNamespace(req=SimpleNamespace(wall_first_token=f,
                                                  wall_admit=w))
              for f, w in ((1.0, 1.5), (2.0, 2.25), (-1.0, -1.0),
                           (9.0, 9.5))]
    assert PT.handoff_waits_ms(tracks, 1.0, 3.0) == [500.0, 250.0]


def test_spans_reach_the_profilers_trace():
    from repro.serving import trace
    d = tempfile.mkdtemp(prefix="program-trace-test-")
    jax.profiler.start_trace(d)
    trace.enable()
    try:
        with trace.span("pd.test.outer"):
            with trace.span("pd.test.inner"):
                jnp.ones(4).block_until_ready()
    finally:
        trace.enable(False)
        jax.profiler.stop_trace()
    evs = list(PT.program_events(TR.Trace(d)))
    got = {e[2]: (e[3], e[3] + e[4]) for e in evs}
    assert set(got) == {"pd.test.outer", "pd.test.inner"}
    assert all(e[0] == "prog" for e in evs)
    (a, b), (c, e) = got["pd.test.outer"], got["pd.test.inner"]
    assert a <= c <= e <= b
