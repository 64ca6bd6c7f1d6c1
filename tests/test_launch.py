"""Launcher + distribution-spec coverage: CLI smoke runs and in-process
lowering of the step functions against a (1-device) mesh via input_specs —
the same code path the 512-device dry-run exercises."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from conftest import reduced_params
from repro.launch.mesh import make_test_mesh
from repro.launch.specs import input_specs
from repro.models.config import ShapeConfig
from repro.models.steps import (decode_window, make_prefill_step,
                                make_serve_step, make_train_step)


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_input_specs_lower_on_mesh(kind):
    cfg, _ = reduced_params("granite-3-8b")
    mesh = make_test_mesh()
    shape = ShapeConfig("t", 64, 4, kind)
    args, shardings = input_specs(cfg, shape, mesh)
    if kind == "train":
        step = make_train_step(cfg, mesh=mesh)
        donate = (0, 1)
    elif kind == "prefill":
        step = make_prefill_step(cfg, mesh=mesh)
        donate = ()
    else:
        step = make_serve_step(cfg, window=decode_window(cfg, shape),
                               mesh=mesh)
        donate = (1,)
    with mesh:
        compiled = jax.jit(step, in_shardings=shardings,
                           donate_argnums=donate).lower(*args).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0


def test_microbatched_train_step_matches_plain():
    """Gradient accumulation must give the same loss metric and close
    parameter updates as the monolithic step."""
    import numpy as np
    from repro.data import SyntheticLM
    from repro.training.optimizer import adamw_init
    cfg, params = reduced_params("minicpm-2b")
    data = SyntheticLM(cfg.vocab_size, 32, 8, seed=2)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    opt = adamw_init(params)
    p1, _, m1 = jax.jit(make_train_step(cfg))(params, opt, batch)
    p2, _, m2 = jax.jit(make_train_step(cfg, microbatches=4))(
        params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-3
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)


ROOT = Path(__file__).resolve().parents[1]


def _cpu_env():
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    for k in ("HOME", "TMPDIR"):
        if k in os.environ:
            env[k] = os.environ[k]
    return env


def _run(mod, *args):
    return subprocess.run(
        [sys.executable, "-m", mod, *args],
        capture_output=True, text=True, timeout=500,
        env=_cpu_env(), cwd=ROOT).returncode


def test_train_cli_smoke():
    rc = _run("repro.launch.train", "--arch", "minicpm-2b", "--reduced",
              "--steps", "25", "--batch", "4", "--seq", "64",
              "--lr", "3e-3")
    assert rc == 0


def test_serve_cli_smoke():
    rc = _run("repro.launch.serve", "--arch", "mamba2-2.7b", "--reduced",
              "--requests", "4", "--max-new-tokens", "3")
    assert rc == 0


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu():
    """The chip smoke run has no CPU path: it exits non-zero and prints
    no result line."""
    p = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, timeout=300,
                       env=_cpu_env(), cwd=ROOT)
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_chip_smoke_checks_pass_on_reduced_cpu_run():
    """Rehearsal of the chip smoke run at the reduced config: the served
    streams agree with the plain reference and every check passes."""
    cs = _chip_smoke()
    cfg, params = reduced_params("granite-3-8b")
    rep = cs.smoke(cfg, params, requests=2, max_new_tokens=4)
    assert rep["ok"], rep
    assert rep["reference"]["steps"] == 2 * 5
    assert rep["reference"]["agree"] == rep["reference"]["steps"]


def test_chip_smoke_reference_catches_a_wrong_token():
    """The reference check fails a stream whose token the reference
    ranks far below its top-1."""
    import numpy as np
    from repro.launch.serve import serve
    cs = _chip_smoke()
    cfg, params = reduced_params("granite-3-8b")
    reqs = serve(cfg, params=params, requests=1, max_new_tokens=4).requests
    assert cs.check_streams(cfg, params, reqs)["ok"]
    r = reqs[0]
    t = 2                                  # corrupt the third served token
    ctx = list(r.tokens) + list(r.generated[:t])
    row = np.asarray(cs.reference_logits(
        cfg, params, jnp.asarray([ctx], jnp.int32)))[0, -1]
    r.generated[t] = int(row.argmin())
    rep = cs.check_streams(cfg, params, reqs)
    assert not rep["ok"]
    assert rep["failures"][0][:2] == (r.rid, t)
