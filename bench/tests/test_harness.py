"""The harness end to end on the CPU at a tiny size, through the same
steps as a chip run (``run.run`` with the look for a chip skipped), and
with the timed path broken underneath, where ``correct`` has to come out
false."""
from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness as H
from bench import run as R

DATA = Path(__file__).resolve().parent / "data"
CELL = "tiny.open"
LIMIT = H.load_json(DATA / "tiny.json")["past_near_tie_limit_pct"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-like directory whose BENCHMARK.json holds one tiny cell."""
    r = tmp_path_factory.mktemp("root")
    (r / "bench" / "configs").mkdir(parents=True)
    (r / "bench" / "traffic").mkdir(parents=True)
    shutil.copy(DATA / "tiny.json", r / "bench" / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny_open.json", r / "bench" / "traffic" / "tiny_open.json")
    shutil.copy(H.BENCH / "traffic" / "gen_requests.py",
                r / "bench" / "traffic" / "gen_requests.py")
    shutil.copytree(H.BENCH / "families", r / "bench" / "families",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "test"}]
    b["workloads"] = [{"name": CELL, "config": "tiny", "chips": 1,
                       "traffic": "tiny_open", "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL]
    (r / "BENCHMARK.json").write_text(json.dumps(b))
    return r


def _run(root, seed=3_000_000_001, seconds=4.0):
    a = R.parse(["--workload", CELL, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", "0"])
    return R.run(a, require_tpu=False, root=root)


def test_sound_run_is_correct_and_builds_nothing_in_the_window(root, capsys):
    out = _run(root)
    err = capsys.readouterr().err
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] == 16
    assert set(out["metrics"]) == {"setup_s", "tpot_p95_ms", "output_tok_s"}
    assert list(out)[-1] == "check"
    assert "programs built in the window: 0 []" in err
    assert out["check"]["checked_tokens"]["value"] >= H.MIN_CHECKED


def test_altered_token_is_caught(root, altered_tokens):
    out = _run(root)
    assert not out["correct"]
    assert out["check"]["past_near_tie_pct"]["value"] > LIMIT


def test_dropped_kv_hand_off_is_caught(root, monkeypatch):
    from repro.serving.kvcache import PagedKVPool
    monkeypatch.setattr(PagedKVPool, "scatter_layer",
                        lambda self, buf, blocks, layer: None)
    out = _run(root)
    assert not out["correct"]
    assert out["check"]["past_near_tie_pct"]["value"] > LIMIT


def test_decode_step_that_keeps_its_cache_unchanged_is_caught(root,
                                                              monkeypatch):
    """The step's KV writes are thrown away: it returns the pool it was
    given, so later tokens attend to stale rows."""
    from repro.serving import engine

    real = engine.decode_step_jit

    def unchanged(cfg, params, storage, *args, **kw):
        keep = jnp.copy(storage)
        out = real(cfg, params, storage, *args, **kw)
        return out[0], out[1], out[2], keep, out[4]

    monkeypatch.setattr(engine, "decode_step_jit", unchanged)
    out = _run(root)
    assert not out["correct"]
    assert out["check"]["past_near_tie_pct"]["value"] > LIMIT


def test_command_refuses_the_cpu():
    """Without a TPU the command exits non-zero and prints no result."""
    p = subprocess.run(
        [sys.executable, str(H.ROOT / "bench" / "run.py"), "--workload",
         "granite-l8.ragged_open", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
