"""Production serving driver: batched prefill + decode engine.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Generates twice from the same prompts: the first call's time includes
compiling, the second is the steady one, and the two must agree token for
token (decoding is greedy).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import canon, get_config, get_smoke_config
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import build_model, build_smoke
from repro.models.layers import unbox
from repro.models.sharding import use_sharding
from repro.serve import make_decode_step, make_prefill_step


class Engine:
    """Minimal batched engine: one prefill, then token-by-token decode with a
    capacity-allocated cache (prefill writes into the decode cache slots)."""

    def __init__(self, model, params, batch: int, max_len: int):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.batch = batch
        self._prefill = jax.jit(make_prefill_step(model))
        self._decode = jax.jit(make_decode_step(model), donate_argnums=(1,))

    def generate(self, tokens: jax.Array, gen: int, extra=None):
        b, s = tokens.shape
        cache0 = self.model.init_cache(b, self.max_len)
        # prefill exactly the s prompt tokens, then decode
        batch = {"tokens": tokens, **(extra or {})}
        nxt, cache = self._prefill(self.params, batch, cache0)
        # the prefill cache holds s positions: pad each leaf at the end out
        # to the decode capacity cache0 was allocated with
        cache = jax.tree.map(
            lambda a, full: jnp.pad(a, [(0, f - n) for n, f
                                        in zip(a.shape, full.shape)]),
            cache, cache0)
        out = [nxt]
        lengths = jnp.full((b,), s, jnp.int32)
        cur = nxt
        for _ in range(gen - 1):
            cur, cache = self._decode(self.params, cache, cur, lengths)
            lengths = lengths + 1
            out.append(cur)
        return jnp.concatenate(out, axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args(argv)

    arch = canon(args.arch)
    cfg = get_smoke_config(arch) if args.smoke else get_config(arch)
    model = build_smoke(cfg) if args.smoke else build_model(cfg)
    mesh = make_production_mesh() if args.production_mesh \
        else make_smoke_mesh(1, 1)

    with use_sharding(mesh):
        # one compiled program writes the parameters straight in their
        # dtype; run eagerly, each bf16 matrix would first exist in f32
        params = jax.jit(lambda key: unbox(model.init(key))[0])(
            jax.random.PRNGKey(0))
        eng = Engine(model, params, args.batch,
                     args.prompt_len + args.gen)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (args.batch, args.prompt_len), 0,
                                    cfg.vocab)
        extra = {}
        if cfg.enc_dec:
            extra["frames"] = jnp.zeros((args.batch, cfg.encoder_seq,
                                         cfg.d_model))
        if cfg.frontend == "vision":
            extra["vision_embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_tokens, cfg.d_model))
        # the first call traces and compiles prefill and decode; the second
        # runs the same shapes compiled
        t0 = time.perf_counter()
        first = jax.block_until_ready(eng.generate(tokens, args.gen, extra))
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(eng.generate(tokens, args.gen, extra))
        steady_s = time.perf_counter() - t0
        if not np.array_equal(np.asarray(first), np.asarray(out)):
            raise RuntimeError("greedy decoding gave different tokens for "
                               "the same prompts on a repeated call")
        print(f"generated {out.shape}: first call (compiling) "
              f"{first_s:.2f}s, steady call {steady_s:.2f}s "
              f"({args.batch * args.gen / steady_s:.1f} tok/s)")
        print("sample:", np.asarray(out[0][:12]))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
