"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up: the cell's weights are made on the device from the seed, the
serving program (``ClusterFrontend``, one process) is built as the
configuration file says, and every shape the cell's traffic uses is
compiled and run once. Then the window: requests are submitted when they
fall due on the host's clock, and every token is stamped as it reaches
the client. After the window the served tokens of every finished
request are held to a plain float32 reference.

Standard error ends with the numbers compared, each beside its limit;
the last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``check`` last). With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window. Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness as H  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = H.load_module(cell.root / "bench" / "metrics"
                               / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(a, *, require_tpu: bool = True, t_start: float = T_START,
        root: Path = H.ROOT, after=None) -> dict:
    """One run; returns the result object (and prints its lines).
    ``after(cell, params, run, seed)``, when given, is called once the
    window's requests are checked, with the run's weights still alive
    (the control readings in ``bench/control.py`` use it)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    cell = H.load_cell(a.workload, root)
    dev = H.device_info(cell.chips, require_tpu)
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    # keep every program, the small per-length ones too, so that a later
    # run of the cell in this checkout builds nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    peaks = H.peaks_for(dev["kind"]) if require_tpu else None
    H.log(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}; compile cache: {cache}")

    traffic = H.generate(cell, a.seed, a.seconds)
    serve = cell.config["serve"]
    H.check_sizes(serve, traffic)
    params = H.weights.make(cell.config, a.seed)
    H.check_weights(cell.config, params)
    fe = H.build(cell, params, a.seed)
    compiles = H.CompileCounter()
    H.serve_waves(fe, cell.config["vocab_size"],
                  H.warm_plan(serve, traffic),
                  np.random.default_rng([a.seed, 1]),
                  traffic["spare_first"])
    gc.collect()

    trace_dir = None
    annotate = None
    if a.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no per-function host events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    c0 = H.counters(fe)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    ended = {}

    def on_end():
        ended["t"] = time.perf_counter()
        ended["counters"] = H.counters(fe)
        if a.trace:
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()

    win = jax.profiler.TraceAnnotation("bench.window") if a.trace else None
    if win:
        win.__enter__()
    run_ = H.drive(fe, traffic, t0, a.seconds, annotate=annotate,
                   on_end=on_end)
    t_end, c1 = ended["t"], ended["counters"]
    in_window = compiles.between(t0, min(t_end, t0 + a.seconds))
    late = run_["late"]
    H.log(f"window: {a.seconds} s from set-up end; requests due "
          f"{len(traffic['due_s'])}, submitted {run_['submitted']}; "
          f"generator late by max {max(late) * 1e3 if late else 0:.3f} ms, "
          f"p99 {H.pct(late, 99) * 1e3 if late else 0:.3f} ms; host in "
          f"serve() {run_['host_busy_s']:.3f} s; programs built in the "
          f"window: {len(in_window)} {sorted(set(in_window))}")

    tracks = run_["tracks"]
    delta = {k: c1[k] - c0[k] for k in c0}
    delta["max_slots"] = c1["max_slots"]
    attempted = len(tracks)
    failed = sum(1 for t in tracks if t.req.shed or not t.req.done)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": H.peak_bytes(dev["_devices"])}

    if a.trace:
        from bench import trace_reduce
        red = trace_reduce.reduce(trace_reduce.Trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red is None:
            raise SystemExit("the trace holds no device operation in the "
                             "window")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"trace": red, "counters": delta, "config": cell.config,
               "serve": serve, "peaks": peaks,
               "work": H.window_work(run_, t0, t_end),
               "memory_peak_bytes": device["memory_peak_bytes"]}
        metrics = per_layer(cell, ctx)
        breakdown = trace_reduce.breakdown(red)
    else:
        e2e = H.end_to_end(run_, t0, a.seconds)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
        breakdown = None
    H.log(f"set-up {setup_s:.3f} s; counters over the window: "
          f"{json.dumps(delta)}")

    del fe
    gc.collect()
    checks = H.check(cell, params, run_)
    if after is not None:
        after(cell, params, run_, a.seed)
    correct = all(H.holds(c) for c in checks.values()) and failed == 0
    for name, c in checks.items():
        H.log(f"check {name}: {c['value']} (limit {c['rule']} {c['limit']})")
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = checks
    print(json.dumps(H.finite(out)), flush=True)
    return out


def main(argv=None) -> int:
    a = parse(argv)
    run(a)
    return 0


if __name__ == "__main__":
    sys.exit(main())
