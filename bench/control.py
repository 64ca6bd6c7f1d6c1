"""Readings that the correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process, a whole benchmark run of the cell (weights
from the seed, warm-up, a window at the cell's own load, the check), and
then, on the same finished requests, the control: the plain reference
computed in bfloat16 (weights and activations), the nearest precision
below the configuration's float32. At every served position it reads the
gap, below the best logit of the float32 reference at the
configuration's matmul precision, of the token the control ranks first.
One JSON line per seed gives the program's and the control's share of
tokens past the near-tie (the number compared), widest and mean gap and
share of positions off the reference's top token. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness as H  # noqa: E402
from bench import reference  # noqa: E402
from bench import run as R  # noqa: E402


def stats(gaps, near_tie: float) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:
        return {"positions": 0}
    return {"past_near_tie_pct": H.past_near_tie_pct(gaps, near_tie),
            "max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99)),
            "off_top": float((g > 0).mean()), "positions": int(g.size)}


def readings(cell, params, run, seed, out):
    streams = H.checked_streams(run)
    g = reference.served_gaps(cell.config, params, streams,
                              control=jnp.bfloat16)
    tie = cell.config["near_tie_logit"]
    out.append({"seed": seed, "program": stats(g["served"], tie),
                "control": stats(g["control"], tie)})
    print(json.dumps(out[-1]), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    out = []
    for seed in a.seeds:
        args = R.parse(["--workload", a.workload, "--seed", str(seed),
                        "--seconds", str(a.seconds), "--trace", "0"])
        R.run(args, t_start=T_START,
              after=lambda c, p, r, s: readings(c, p, r, s, out))
    key = "past_near_tie_pct"
    print(json.dumps({"program_largest": max(o["program"][key] for o in out),
                      "control_smallest": min(o["control"][key]
                                              for o in out)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
