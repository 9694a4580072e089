"""Elastic training driver: failure detection → mesh shrink → restore →
continue; growth is the same flow in reverse.

This is the end-to-end wiring of the fault-tolerance substrate:
ElasticController (health/plan) + Checkpointer (mesh-agnostic restore) +
the stateless data pipeline (replay from step counters). The demo entry
point simulates losing half the data-parallel axis mid-run and continues on
the survivors, bit-identically to a run that never used the lost chips
(per-step determinism comes from (seed, step), not from world size).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.elastic_train --steps 12 --fail-at 6
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import canon, get_smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.distributed import ElasticController
from repro.launch.mesh import opt_specs
from repro.models import build_smoke
from repro.models.layers import unbox
from repro.models.sharding import use_sharding
from repro.train import (AdamWConfig, TrainConfig, abstract_train_state,
                         init_train_state, make_train_step)


def _mesh_for(devices):
    return jax.sharding.Mesh(np.array(devices).reshape(len(devices), 1),
                             ("data", "model"))


def run_elastic(arch: str = "yi_9b", steps: int = 12, fail_at: int = 6,
                ckpt_dir: str = "/tmp/repro_elastic", seed: int = 0):
    """Returns (losses, world_sizes) across the failure boundary."""
    cfg = get_smoke_config(arch)
    model = build_smoke(cfg)
    tcfg = TrainConfig(opt=AdamWConfig(lr_peak=1e-3, warmup_steps=2,
                                       total_steps=steps))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=8, seed=seed))
    ck = Checkpointer(ckpt_dir, keep=2, async_save=False)
    all_devices = jax.devices()
    ec = ElasticController(range(len(all_devices)), heartbeat_timeout=1e9)

    losses, worlds = [], []

    def train_span(devices, start, end, restore):
        mesh = _mesh_for(devices)
        with use_sharding(mesh):
            step_fn = jax.jit(make_train_step(model, tcfg),
                              donate_argnums=(0,))
            if restore:
                abs_state = abstract_train_state(model)
                state = ck.restore_latest(abs_state)
            else:
                state = init_train_state(model, jax.random.PRNGKey(seed))
            for i in range(start, end):
                batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
                state, metrics = step_fn(state, batch)
                losses.append(float(metrics["loss"]))
                worlds.append(len(devices))
            ck.save(end, state)
        return state

    # healthy span on the full world
    train_span(all_devices, 0, fail_at, restore=False)

    # failure: half the data axis goes silent → shrink plan → resume from
    # the last committed checkpoint on the survivors
    n_dead = len(all_devices) // 2
    for w in range(len(all_devices) - n_dead, len(all_devices)):
        ec.health[w].last_heartbeat = -1.0
        ec.health[w].alive = False
    survivors = all_devices[:len(all_devices) - n_dead]
    train_span(survivors, fail_at, steps, restore=True)
    return losses, worlds


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--fail-at", type=int, default=6)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_elastic")
    args = ap.parse_args(argv)
    losses, worlds = run_elastic(canon(args.arch), args.steps, args.fail_at,
                                 args.ckpt_dir)
    for i, (l, w) in enumerate(zip(losses, worlds)):
        marker = "  <- shrunk world" if i and worlds[i - 1] != w else ""
        print(f"step {i:3d} world={w} loss={l:.4f}{marker}")
    print("elastic run complete")
    return losses, worlds


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
