"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --smoke \
        --steps 100 --over-decompose 4 --checkpoint-dir /tmp/ck

On a real slice the production mesh is built from the flags; in this CPU
container ``--smoke`` uses the reduced config on a 1×1 mesh. Fault tolerance:
checkpoints every ``--ckpt-every`` steps (async, rotated), automatic resume
from the latest committed step, stateless data pipeline keyed by (seed, step).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import canon, get_config, get_smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_production_mesh, make_smoke_mesh, opt_specs
from repro.models import build_model, build_smoke
from repro.models.layers import unbox
from repro.models.sharding import use_sharding
from repro.models.transformer import Flags
from repro.train import (AdamWConfig, TrainConfig, abstract_train_state,
                         init_train_state, make_train_step)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU container)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--over-decompose", type=int, default=1,
                    help="microbatches per step (paper over-decomposition)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    arch = canon(args.arch)
    cfg = get_smoke_config(arch) if args.smoke else get_config(arch)
    model = build_smoke(cfg) if args.smoke else build_model(cfg)
    mesh = make_production_mesh(multi_pod=args.multi_pod) \
        if args.production_mesh else make_smoke_mesh(1, 1)

    tcfg = TrainConfig(
        opt=AdamWConfig(lr_peak=args.lr, warmup_steps=max(args.steps // 20, 5),
                        total_steps=args.steps, weight_decay=0.01),
        over_decompose=args.over_decompose)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                                  global_batch=args.global_batch))

    with use_sharding(mesh):
        step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
        state = init_train_state(model, jax.random.PRNGKey(0))
        start = 0
        ck = None
        if args.checkpoint_dir:
            ck = Checkpointer(args.checkpoint_dir, keep=3)
            latest = ck.latest_step()
            if latest is not None:
                abs_state = abstract_train_state(model)
                state = ck.restore(latest, abs_state)
                start = latest
                print(f"resumed from step {latest}")

        t0 = time.time()
        for i in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
            if cfg.frontend == "vision":
                batch["vision_embeds"] = jnp.zeros(
                    (args.global_batch, cfg.frontend_tokens, cfg.d_model))
            if cfg.enc_dec:
                batch["frames"] = jnp.zeros(
                    (args.global_batch, cfg.encoder_seq, cfg.d_model))
            state, metrics = step_fn(state, batch)
            if (i + 1) % args.log_every == 0:
                dt = (time.time() - t0) / args.log_every
                tok_s = args.global_batch * args.seq_len / dt
                print(f"step {i+1:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"{dt*1e3:.0f} ms/step {tok_s:.0f} tok/s", flush=True)
                t0 = time.time()
            if ck and (i + 1) % args.ckpt_every == 0:
                ck.save(i + 1, state)
        if ck:
            ck.save(args.steps, state, block=True)
        print("done")
    return state


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
