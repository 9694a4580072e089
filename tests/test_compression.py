"""Cross-pod gradient compression: numerics + convergence tracking."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.compression import dequantize_int8, quantize_int8

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(3)


@pytest.mark.parametrize("shape", [(7,), (3, 300), (2, 5, 129), (256,)])
def test_quantize_roundtrip_error_bounded(shape):
    x = jax.random.normal(KEY, shape) * 3.0
    q, s, _ = quantize_int8(x)
    back = dequantize_int8(q, s, x.shape, jnp.float32)
    # symmetric int8: error ≤ scale/2 = max|block|/254 per element
    err = jnp.abs(back - x)
    bound = jnp.max(jnp.abs(x)) / 127.0
    assert float(jnp.max(err)) <= float(bound) + 1e-6


def test_compressed_training_tracks_exact():
    """8 virtual devices, (pod=2, data=2, model=2): compressed-gradient
    training must track exact training closely (error feedback). The
    gradient runs in a shard_map manual over 'pod' only (see
    train_step._compressed_grads)."""
    code = """
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import build_smoke
        from repro.models.sharding import use_sharding
        from repro.train import (TrainConfig, init_train_state,
                                 make_train_step, AdamWConfig)
        from repro.data import DataConfig, SyntheticLM
        cfg = get_smoke_config('yi_9b')
        m = build_smoke(cfg)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        opt = AdamWConfig(lr_peak=1e-3, warmup_steps=2, total_steps=50)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      global_batch=8, seed=5))
        with use_sharding(mesh):
            s_ex = init_train_state(m, jax.random.PRNGKey(0))
            step_ex = jax.jit(make_train_step(m, TrainConfig(opt=opt)))
            s_cp = init_train_state(m, jax.random.PRNGKey(0), ef_pods=2)
            step_cp = jax.jit(make_train_step(
                m, TrainConfig(opt=opt, compress_pod_grads=True)))
            for i in range(6):
                b = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
                s_ex, m_ex = step_ex(s_ex, b)
                s_cp, m_cp = step_cp(s_cp, b)
            d = max(float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - c.astype(jnp.float32))))
                for a, c in zip(jax.tree.leaves(s_ex.params),
                                jax.tree.leaves(s_cp.params)))
            assert d < 0.02, d
            assert abs(float(m_ex['loss']) - float(m_cp['loss'])) < 0.05
            print('compressed tracks exact, max param delta', d)
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=900, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "compressed tracks exact" in out.stdout
