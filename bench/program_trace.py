"""The serving program's own spans and build counter, read from a traced run.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

Runs ``bench/run.py``'s traced run of the cell (``--trace 1``) with the
program's spans turned on (``repro.serving.trace.enable()``), and prints,
after that run's own lines, one JSON line of what the program's spans,
its build counter and its request stamps show over the window:

- ``prog_spans``: union seconds and count of each ``pd.*`` span, clipped
  to the window, and of each group ``pd.<layer>.*``;
- ``idle_by_program``: idle device seconds, keyed by the innermost
  ``pd.*`` span open at the middle of each gap (``"pd: none"`` if none);
- ``named_idle_share``: of the idle time inside the benchmark's
  ``bench.serve.*`` spans, the share charged to a named ``pd.*`` span;
- ``clock_offset_ms``: bounds on how late the device timeline runs
  against the host's, which the decode steps set (a step's program runs
  between the opening of its span and the end of its read-back), and
  the offset by which the device timeline is moved back before idle
  time is charged;
- ``inside``: the share of the window's runs of the prefill and decode
  programs that start and end inside the span that dispatches them and
  reads their result back, with the device timeline moved back and as
  recorded (``share_raw``);
- ``builds``: programs built in the window, by span, beside the loads
  from the persistent compilation cache counted in the same window;
- ``metrics``: ``kv_handoff_host_ms_per_req``,
  ``kv_handoff_builds_per_req``, ``handoff_wait_ms_p50`` and
  ``decode_host_ms_per_step`` (PERF.md, section 3).

Without a TPU the run exits non-zero, as ``bench/run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Iterable, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness as H  # noqa: E402
from bench import run as R  # noqa: E402
from bench import trace_reduce  # noqa: E402
from bench.metrics_common import DECODE_MODULES  # noqa: E402

PROG = "pd."
NONE = "pd: none"
# each program and the span that dispatches it and reads its result back
INSIDE = {"jit_forward_decode_step": "pd.decode.step",
          "jit_forward_prefill": "pd.prefill.batch"}
READBACK = "pd.decode.readback"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_ID = re.compile(r"\(\d+\)$")


def program_events(tr: trace_reduce.Trace) -> Iterable[trace_reduce.Event]:
    """The ``pd.*`` host spans of a trace, as ``("prog", ...)`` events."""
    for plane in tr.data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROG):
                    yield ("prog", line.name, ev.name, int(ev.start_ns),
                           int(ev.duration_ns))


class Spans:
    """Properly nested spans (one thread), for innermost-span lookups."""

    def __init__(self, spans: List[Tuple[int, int, str]]):
        spans = sorted(spans, key=lambda s: (s[0], -s[1]))
        self.starts = [s[0] for s in spans]
        self.ends = [s[1] for s in spans]
        self.names = [s[2] for s in spans]
        self.parent: List[int] = []
        open_: List[int] = []
        for i, (a, b, _) in enumerate(spans):
            while open_ and self.ends[open_[-1]] < b:
                open_.pop()
            self.parent.append(open_[-1] if open_ else -1)
            open_.append(i)

    def innermost(self, t: int, default: str) -> str:
        k = bisect.bisect_right(self.starts, t) - 1
        while k >= 0 and self.ends[k] < t:
            k = self.parent[k]
        return self.names[k] if k >= 0 else default


def union_s(iv: List[Tuple[int, int]]) -> float:
    """Seconds covered by a list of (start, end) nanosecond intervals."""
    total, reach = 0, None
    for a, b in sorted(iv):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total * 1e-9


def idle_gaps(lo: List[int], hi: List[int], w0: int, w1: int
              ) -> List[Tuple[int, int]]:
    """The gaps between busy intervals inside [w0, w1]."""
    a = np.asarray(lo, np.int64)
    b = np.asarray(hi, np.int64)
    order = np.argsort(a, kind="stable")
    reach = np.maximum.accumulate(b[order])
    ends = np.concatenate([[w0], reach])
    begins = np.concatenate([a[order], [w1]])
    open_ = begins > ends
    return list(zip(ends[open_].tolist(), begins[open_].tolist()))


def clock_offset(runs: List[Tuple[str, int, int]],
                 prog: List[Tuple[int, int, str]]
                 ) -> Optional[Tuple[int, int]]:
    """Bounds (ns) on how late the trace's device timeline runs against
    its host timeline, from the decode steps: each step's program run
    starts after its ``pd.decode.step`` span opens and ends before the
    step's ``pd.decode.readback`` span closes. The i-th run pairs with
    the i-th step (one run, one read-back each); None when the counts
    differ."""
    mod = DECODE_MODULES[0]
    dec = sorted((a, b) for m, a, b in runs if m == mod)
    steps = sorted((a, b) for a, b, n in prog if n == INSIDE[mod])
    backs = sorted((a, b) for a, b, n in prog if n == READBACK)
    if not dec or not len(dec) == len(steps) == len(backs):
        return None
    lo = max(r[1] - rb[1] for r, rb in zip(dec, backs))
    hi = min(r[0] - st[0] for r, st in zip(dec, steps))
    return lo, hi


def reduce_program(evs: Iterable[trace_reduce.Event],
                   window: Optional[Tuple[int, int]] = None
                   ) -> Optional[Dict]:
    """What the program's spans show inside the window span (or
    ``window``). The device busy time is taken as ``trace_reduce.reduce``
    takes it, from the operations, once the device timeline is moved
    back by the offset nearest zero that ``clock_offset`` allows.
    ``inside`` gives each program's share of runs inside its span with
    and without that move."""
    bench, prog = [], []
    ops: Dict[str, List[Tuple[int, int]]] = {}
    runs: List[Tuple[str, int, int]] = []
    evs = list(evs)
    for kind, line, name, start, dur in evs:
        if kind == "host" and name == trace_reduce.WINDOW_SPAN \
                and window is None:
            window = (start, start + dur)
    if window is None:
        return None
    w0, w1 = window
    for kind, line, name, start, dur in evs:
        end = start + dur
        if kind == "host" and name != trace_reduce.WINDOW_SPAN:
            bench.append((start, end, name))
        elif kind == "prog":
            prog.append((start, end, name))
        elif kind.startswith("device:"):
            if line == "XLA Modules":
                mod = _ID.sub("", name).strip()
                if mod in INSIDE:
                    runs.append((mod, start, end))
            elif line == "XLA Ops":
                ops.setdefault(kind, []).append((start, end))
    bound = clock_offset(runs, prog)
    # the offset nearest zero within the bounds (the lower one if they
    # cross)
    off = min(max(0, bound[0]), max(bound)) if bound else 0
    busy: Dict[str, Tuple[List[int], List[int]]] = {}
    for kind, iv in ops.items():
        for start, end in iv:
            a, b = max(start - off, w0), min(end - off, w1)
            if b > a:
                lo, hi = busy.setdefault(kind, ([], []))
                lo.append(a)
                hi.append(b)
    if not busy:
        return None
    by_name: Dict[str, List[Tuple[int, int]]] = {}
    for a, b, name in prog:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            by_name.setdefault(name, []).append((a, b))
            group = name.rsplit(".", 1)[0] + ".*"
            by_name.setdefault(group, []).append((a, b))
    prog_spans = {k: {"s": union_s(v), "n": len(v)}
                  for k, v in by_name.items()}
    bench_sp, prog_sp = Spans(bench), Spans(prog)
    idle: Dict[str, float] = {}
    serve_idle = named = 0.0
    for lo, hi in busy.values():
        for g0, g1 in idle_gaps(lo, hi, w0, w1):
            mid, s = (g0 + g1) // 2, (g1 - g0) * 1e-9
            where = prog_sp.innermost(mid, NONE)
            idle[where] = idle.get(where, 0.0) + s
            if bench_sp.innermost(mid, "").startswith("bench.serve."):
                serve_idle += s
                named += s if where != NONE else 0.0
    inside: Dict[str, Dict] = {}
    for mod, span in INSIDE.items():
        iv = sorted((a, b) for a, b, name in prog if name == span)
        starts = [a for a, _ in iv]
        mine = [(a, b) for m, a, b in runs
                if m == mod and w0 <= a - off < w1]
        k_in = {0: 0, off: 0}
        for a, b in mine:
            for o in k_in:
                k = bisect.bisect_right(starts, a - o) - 1
                k_in[o] += k >= 0 and iv[k][1] >= b - o
        inside[mod] = {"runs": len(mine),
                       "share": k_in[off] / len(mine) if mine else None,
                       "share_raw": k_in[0] / len(mine) if mine else None}
    return {"window_s": (w1 - w0) * 1e-9, "prog_spans": prog_spans,
            "idle_by_program": {k: v / len(busy) for k, v in idle.items()},
            "named_idle_share": named / serve_idle if serve_idle else None,
            "inside": inside,
            "clock_offset_ms": {"lo": bound[0] * 1e-6, "hi": bound[1] * 1e-6,
                                "used": off * 1e-6} if bound else None}


def handoff_waits_ms(tracks, a: float, b: float) -> List[float]:
    """First token to decode admission, in ms, of each request admitted
    between host times a and b."""
    return [(tr.req.wall_admit - tr.req.wall_first_token) * 1e3
            for tr in tracks
            if a <= tr.req.wall_admit <= b and tr.req.wall_first_token > 0]


def metrics(red: Dict, prog: Dict, counters: Dict[str, float],
            waits_ms: List[float]) -> Dict[str, float]:
    """The four numbers of the hand-off and the decode lane that the
    program's spans, build counter and stamps give."""
    out = {}
    handed = counters.get("handed_to_decode", 0.0)
    spans = prog["prog_spans"]
    if handed:
        out["kv_handoff_host_ms_per_req"] = 1e3 * spans.get(
            "pd.xfer.*", {"s": 0.0})["s"] / handed
        out["kv_handoff_builds_per_req"] = counters["xfer_builds"] / handed
    if waits_ms:
        out["handoff_wait_ms_p50"] = float(np.median(waits_ms))
    steps = counters.get("decode_steps", 0.0)
    if steps and "pd.decode.step" in spans:
        dev = trace_reduce.seconds_of(red["modules"], DECODE_MODULES)
        out["decode_host_ms_per_step"] = \
            1e3 * (spans["pd.decode.step"]["s"] - dev) / steps
    return out


def main(argv=None) -> int:
    import jax
    from repro.serving import trace

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    args = R.parse(["--workload", a.workload, "--seed", str(a.seed),
                    "--seconds", str(a.seconds), "--trace", "1"])
    hits = [0]

    def on_event(event, **kw):
        if event == CACHE_HIT_EVENT:
            hits[0] += 1

    jax.monitoring.register_event_listener(on_event)
    # snapshots at the window's start and end: bench/run.py reads the
    # program's counters exactly there
    snaps: List[Tuple[float, Dict, int, int, Dict]] = []
    counters, reduce = H.counters, trace_reduce.reduce
    got: Dict = {}

    def counted(fe):
        c = counters(fe)
        snaps.append((time.perf_counter(), trace.builds(),
                      trace.build_count(), hits[0], c))
        return c

    def reduced(evs, window=None):
        red = reduce(evs, window)
        if red is not None:
            got["red"] = red
            got["prog"] = reduce_program(
                list(evs) + list(program_events(evs)), window)
        return red

    H.counters, trace_reduce.reduce = counted, reduced
    trace.enable()
    try:
        R.run(args, t_start=T_START,
              after=lambda c, p, run_, s: got.update(run=run_))
    finally:
        H.counters, trace_reduce.reduce = counters, reduce
        trace.enable(False)
    (t0, b0, n0, h0, c0), (t1, b1, n1, h1, c1) = snaps[0], snaps[-1]
    span_builds = {k: b1.get(k, 0) - b0.get(k, 0) for k in b1
                   if b1.get(k, 0) != b0.get(k, 0)}
    delta = {k: c1[k] - c0[k] for k in c0}
    delta["xfer_builds"] = float(sum(v for k, v in span_builds.items()
                                     if k.startswith("pd.xfer.")))
    prog = got["prog"]
    run_ = got["run"]
    out = {"metrics": metrics(got["red"], prog, delta, handoff_waits_ms(
        run_["tracks"], t0, t1)),
        "builds": {"total": n1 - n0, "cache_hits": h1 - h0,
                   "by_span": span_builds},
        "host_in_serve_s": run_["host_busy_s"],
        "named_idle_share": prog["named_idle_share"],
        "inside": prog["inside"],
        "clock_offset_ms": prog["clock_offset_ms"],
        "idle_by_program": sorted(prog["idle_by_program"].items(),
                                  key=lambda kv: -kv[1]),
        "prog_spans": prog["prog_spans"]}
    print(json.dumps({"program": H.finite(out)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
