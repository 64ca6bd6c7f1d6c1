"""Training launcher: real steps on CPU (reduced configs / ~100M models) or
AOT lowering against the production mesh (--dry-run goes via dryrun.py).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
      --reduced --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_params
from repro.configs import ALIASES, get_config
from repro.data import SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.models.params import init_params, param_count_actual
from repro.models.steps import make_train_step
from repro.training.optimizer import AdamWConfig, adamw_init


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b", choices=sorted(ALIASES))
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced same-family variant (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. ~100M model)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--save", default="")
    ap.add_argument("--log-every", type=int, default=5)
    a = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(a.arch)
    if a.reduced:
        cfg = cfg.reduced()
    if a.d_model:
        cfg = cfg.replace(d_model=a.d_model,
                          head_dim=max(32, a.d_model // max(cfg.num_heads, 1)))
    if a.layers:
        cfg = cfg.replace(num_layers=a.layers)
    n = param_count_actual(cfg)
    print(f"[train] {cfg.name}: {n/1e6:.1f}M params, "
          f"batch={a.batch} seq={a.seq}")

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    step_fn = jax.jit(make_train_step(cfg, AdamWConfig(lr=a.lr)))
    data = SyntheticLM(cfg.vocab_size, a.seq, a.batch, seed=1)

    t0 = time.time()
    losses = []
    for step in range(a.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % a.log_every == 0 or step == a.steps - 1:
            dt = time.time() - t0
            print(f"  step {step:4d} loss {loss:.4f} "
                  f"grad_norm {float(metrics['grad_norm']):.3f} "
                  f"({dt:.1f}s)")
    if a.save:
        save_params(a.save, params, step=a.steps)
        print(f"[train] saved -> {a.save}")
    improved = losses[-1] < losses[0]
    print(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({'improved' if improved else 'NOT improved'})")
    return 0 if improved else 1


if __name__ == "__main__":
    sys.exit(main())
