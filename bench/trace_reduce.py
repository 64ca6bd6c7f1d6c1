"""From a profiler trace to device busy time, program time and kernel time.

``Trace`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
yields one tuple per event: ``(kind, line, name, start_ns, dur_ns)``,
where ``kind`` is ``"device:<n>"`` for an operation on TPU core ``n``
and ``"host"`` for a host span. ``reduce`` takes such tuples, from a
trace or from a recorded list, and the host span that marks the window,
and returns:

- ``window_s``: the marked window's length;
- ``busy_s``: the union of the intervals in which an operation ran on the
  device, inside the window, averaged over the devices;
- ``modules``: seconds and count of each program (``XLA Modules`` line),
  keyed by its name without the trailing ``(id)``;
- ``ops``: seconds and count of each operation (``XLA Ops`` line), keyed
  ``"<program>|<op> <opcode>"``: the program whose run the operation lies
  in, and the operation's HLO name and opcode (``%fusion.3 fusion``; a
  custom call adds its target, ``tpu_custom_call`` for a Pallas kernel);
- ``idle_by_host``: idle device seconds, keyed by the innermost host
  span of the benchmark (``bench.*``) open at the middle of each gap;
- ``gaps``: the number of idle gaps.

Per-layer metrics pick their programs and kernels out of ``modules`` and
``ops`` by the names they declare.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

import numpy as np

Event = Tuple[str, str, str, int, int]
WINDOW_SPAN = "bench.window"
_ID = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^(%?[\w.\-]+) = .*? ([\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """``%name opcode`` of an HLO instruction's text (with the target of a
    custom call: a Pallas kernel is a ``tpu_custom_call``), or the text
    itself."""
    m = _OP.match(text)
    if not m:
        return text[:80]
    t = _TARGET.search(text)
    return f"{m.group(1)} {m.group(2)}" + (f" {t.group(1)}" if t else "")


class Trace:
    """The events of the newest ``.xplane.pb`` under a directory; each
    iteration reads them again from the loaded trace."""

    def __init__(self, trace_dir: str):
        from jax.profiler import ProfileData
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        self.path = max(files, key=os.path.getmtime)
        self.data = ProfileData.from_file(self.path)

    def __iter__(self) -> Iterator[Event]:
        for plane in self.data.planes:
            pname = plane.name
            if pname.startswith("/device:TPU:") and pname[12:].isdigit():
                kind = "device:" + pname[12:]
                for line in plane.lines:
                    if line.name not in ("XLA Ops", "XLA Modules"):
                        continue
                    for ev in line.events:
                        yield (kind, line.name, ev.name, int(ev.start_ns),
                               int(ev.duration_ns))
            elif pname.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            yield ("host", line.name, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns))


def _label(spans: List[Tuple[int, int, str]], starts: List[int],
           t: int) -> str:
    """Innermost (latest-starting) host span that covers time ``t``."""
    k = bisect.bisect_right(starts, t)
    for a, b, name in reversed(spans[max(0, k - 64):k]):
        if a <= t <= b:
            return name
    return "host: no benchmark span"


def reduce(evs: Iterable[Event], window: Optional[Tuple[int, int]] = None
           ) -> Optional[Dict]:
    """Reduce events to device busy time, program and operation time and
    idle time by host activity, inside the window span (or ``window``).
    ``evs`` is read twice: host spans first, then device events."""
    spans: List[Tuple[int, int, str]] = []
    for kind, line, name, start, dur in evs:
        if kind != "host":
            continue
        if name == WINDOW_SPAN:
            if window is None:
                window = (start, start + dur)
        else:
            spans.append((start, start + dur, name))
    if window is None:
        return None
    w0, w1 = window
    iv: Dict[str, Tuple[List[int], List[int]]] = {}
    runs: Dict[str, List[Tuple[int, int, str]]] = {}
    modules: Dict[str, List[float]] = {}
    for kind, line, name, start, dur in evs:
        if kind == "host" or line != "XLA Modules":
            continue
        mod = _ID.sub("", name).strip()
        runs.setdefault(kind, []).append((start, start + dur, mod))
        a, b = max(start, w0), min(start + dur, w1)
        if b <= a:
            continue
        acc = modules.setdefault(mod, [0.0, 0])
        acc[0] += (b - a) * 1e-9
        acc[1] += 1
    for r in runs.values():
        r.sort()
    run_starts = {k: [r[0] for r in v] for k, v in runs.items()}
    ops: Dict[str, List[float]] = {}
    for kind, line, name, start, dur in evs:
        if kind == "host" or line != "XLA Ops":
            continue
        a, b = max(start, w0), min(start + dur, w1)
        if b <= a:
            continue
        mod = "?"
        k = bisect.bisect_right(run_starts.get(kind, []), start) - 1
        if k >= 0 and runs[kind][k][1] >= start:
            mod = runs[kind][k][2]
        acc = ops.setdefault(f"{mod}|{op_name(name)}", [0.0, 0])
        acc[0] += (b - a) * 1e-9
        acc[1] += 1
        lo, hi = iv.setdefault(kind, ([], []))
        lo.append(a)
        hi.append(b)
    if not iv:
        return None
    spans.sort()
    starts = [s[0] for s in spans]
    busy, idle, gaps = 0.0, {}, 0
    for lo, hi in iv.values():
        a = np.asarray(lo, np.int64)
        b = np.asarray(hi, np.int64)
        order = np.argsort(a, kind="stable")
        a, b = a[order], b[order]
        reach = np.maximum.accumulate(b)
        # gap i: between the furthest end so far and the next start
        ends = np.concatenate([[w0], reach])
        begins = np.concatenate([a, [w1]])
        open_ = begins > ends
        busy += (w1 - w0 - (begins[open_] - ends[open_]).sum()) * 1e-9
        for g0, g1 in zip(ends[open_].tolist(), begins[open_].tolist()):
            gaps += 1
            name = _label(spans, starts, (g0 + g1) // 2)
            idle[name] = idle.get(name, 0.0) + (g1 - g0) * 1e-9
    n = len(iv)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n,
        "devices": n,
        "modules": {k: {"s": v[0], "n": v[1]} for k, v in modules.items()},
        "ops": {k: {"s": v[0], "n": v[1]} for k, v in ops.items()},
        "idle_by_host": {k: v / n for k, v in idle.items()},
        "gaps": gaps,
    }


def _match(key: str, patterns) -> bool:
    """A pattern is a string (the key contains it) or a tuple of strings
    (the key contains all of them)."""
    return any(all(q in key for q in ((p,) if isinstance(p, str) else p))
               for p in patterns)


def seconds_of(table: Dict[str, Dict], patterns: Iterable) -> float:
    """Total seconds of the entries whose name matches any pattern."""
    pats = tuple(patterns)
    return sum(v["s"] for k, v in table.items() if _match(k, pats))


def count_of(table: Dict[str, Dict], patterns: Iterable) -> int:
    pats = tuple(patterns)
    return sum(v["n"] for k, v in table.items() if _match(k, pats))


def breakdown(red: Dict, top: int = 10) -> Dict[str, List]:
    """The device operations that took most time and the idle time by
    what the host was doing, at most ``top`` of each."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1]["s"])[:top]
    idle = sorted(red["idle_by_host"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v["s"]] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
