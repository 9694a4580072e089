"""Multi-device behaviour via subprocesses with forced host device counts
(tests must not pollute this process's single-device view).

Covers: scheduler spreading work over 4 devices (paper Fig. 9 semantics),
MoE expert-parallel path vs the dense oracle on a real 8-device mesh, and
SPMD Jacobi on a sharded axis.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 4, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_scheduler_uses_all_devices():
    out = run_py("""
        import numpy as np, json, collections
        from repro.core import Runtime, RuntimeConfig
        with Runtime(RuntimeConfig(scheduler='least_loaded',
                                   memory_capacity=1<<26)) as rt:
            assert len(rt.devices) == 4, len(rt.devices)
            objs = [rt.hetero_object(np.ones((64, 64), np.float32))
                    for _ in range(16)]
            tasks = []
            for o in objs:
                tasks.append(rt.run(lambda v: (v @ v.T).astype(v.dtype),
                                    [(o, 'rw')]))
            rt.barrier()
            used = collections.Counter(t.chosen_device for t in tasks)
            print(json.dumps(dict(used)))
    """)
    used = json.loads(out.strip().splitlines()[-1])
    assert len(used) >= 3, f"work not spread across devices: {used}"


def test_locality_scheduler_prefers_resident_device():
    out = run_py("""
        import numpy as np
        from repro.core import Runtime, RuntimeConfig
        with Runtime(RuntimeConfig(scheduler='locality',
                                   memory_capacity=1<<26)) as rt:
            x = rt.hetero_object(np.ones((128, 128), np.float32))
            t1 = rt.run(lambda v: v + 1, [(x, 'rw')])
            rt.barrier()
            home = t1.chosen_device
            devs = []
            for _ in range(5):
                t = rt.run(lambda v: v + 1, [(x, 'rw')])
                rt.barrier()
                devs.append(t.chosen_device)
            print('HOME', home, devs)
            assert all(d == home for d in devs), (home, devs)
    """)
    assert "HOME" in out


def test_moe_ep_matches_dense_oracle():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import MoEConfig
        from repro.models import moe as M
        from repro.models.layers import unbox
        from repro.models.sharding import use_sharding
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        mcfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32)
        key = jax.random.PRNGKey(0)
        p, _ = unbox(M.moe_init(key, 16, mcfg, True, dtype=jnp.float32))
        x = jax.random.normal(key, (4, 16, 16), jnp.float32)
        want, aux_d = M.moe_dense(p, x, mcfg, True)
        with use_sharding(mesh):
            got, aux_e = jax.jit(
                lambda p, x: M.moe_ep(p, x, mcfg, True,
                                      capacity_factor=8.0))(p, x)
        # capacity_factor=8 → no drops → exact match expected
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 1e-4, err
        print('EP matches dense:', err)
    """, devices=8)


@pytest.mark.parametrize("shape", [(16, 8, 8), (16, 8, 128)])
def test_spmd_jacobi_multidevice(shape):
    """(16, 8, 128) is a grid whose slabs the Pallas stencil takes: its TPU
    body is traced beside the jnp one inside shard_map."""
    run_py(f"""
        import numpy as np, jax
        from repro.apps.jacobi3d import run_reference, run_spmd
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 1), ('data', 'model'))
        rng = np.random.default_rng(0)
        u0 = rng.random({shape}).astype(np.float32)
        want = run_reference(u0, 3)
        for bulk in (False, True):
            got = run_spmd(u0, 3, mesh, axis='data', bulk_sync=bulk)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        print('spmd multidevice ok')
    """)


def test_seq_sharded_decode_matches_plain():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.attention import (decode_attention,
                                            seq_sharded_decode)
        from repro.models.sharding import use_sharding
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 1), ('data', 'model'))
        key = jax.random.PRNGKey(0)
        b, t, kh, g, d = 1, 64, 2, 2, 16
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, kh, g, d))
        kc = jax.random.normal(ks[1], (b, t, kh, d))
        vc = jax.random.normal(ks[2], (b, t, kh, d))
        valid = jnp.arange(t)[None, :] < 50
        want = decode_attention(q, kc, vc, valid=valid)
        with use_sharding(mesh):
            got = jax.jit(lambda *a: seq_sharded_decode(
                a[0], a[1], a[2], valid=a[3], axis='data'))(q, kc, vc, valid)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 1e-5, err
        print('seq-sharded decode ok:', err)
    """)


def test_elastic_restore_to_smaller_mesh(tmp_path):
    """Checkpoint on an 8-device mesh restores onto a 4-device mesh — the
    elastic-rescale path."""
    ckdir = str(tmp_path / "ck")
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.checkpoint import Checkpointer
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ('data',))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh, PS('data')))
        ck = Checkpointer({ckdir!r}, async_save=False)
        ck.save(1, {{'x': x}}, block=True)
        print('saved')
    """, devices=8)
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as PS
        from repro.checkpoint import Checkpointer
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ('data',))
        ck = Checkpointer({ckdir!r})
        abs_state = {{'x': jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
        shardings = {{'x': NamedSharding(mesh, PS('data'))}}
        got = ck.restore(1, abs_state, shardings)
        np.testing.assert_array_equal(np.asarray(got['x']),
                                      np.arange(64.0).reshape(8, 8))
        print('restored on smaller mesh')
    """, devices=4)


def test_sequence_parallel_rules_preserve_numerics():
    """act_seq→model sharding (the SP optimization from §Perf) must not
    change the loss — GSPMD may only reshard, never alter values."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import build_smoke
        from repro.models.layers import unbox
        from repro.launch.mesh import make_mesh
        from repro.models.sharding import use_sharding
        for arch in ('gemma3_27b', 'mamba2_370m'):
            cfg = get_smoke_config(arch)
            m = build_smoke(cfg)
            params, _ = unbox(m.init(jax.random.PRNGKey(0)))
            B, S = 2, 64
            batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1),
                                                  (B, S), 0, cfg.vocab),
                     'labels': jax.random.randint(jax.random.PRNGKey(2),
                                                  (B, S), 0, cfg.vocab)}
            def loss(p, b):
                x, _, aux = m.apply(p, b, mode='train')
                return m.loss(p, x, b['labels']) + aux
            want = float(jax.jit(loss)(params, batch))
            mesh = make_mesh((2, 4), ('data', 'model'))
            with use_sharding(mesh, {'act_seq': 'model'}):
                got = float(jax.jit(loss)(params, batch))
            assert abs(got - want) < 1e-3, (arch, got, want)
            print(arch, 'sp numerics ok', got, want)
    """, devices=8)


def test_seq_sharded_decode_model_axis():
    """kvseq_model variant: seq-sharded cache over the *model* axis with
    batch over data matches plain decode attention."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.attention import (decode_attention,
                                            seq_sharded_decode)
        from repro.models.sharding import use_sharding
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ('data', 'model'))
        key = jax.random.PRNGKey(0)
        b, t, kh, g, d = 4, 32, 2, 2, 16
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, kh, g, d))
        kc = jax.random.normal(ks[1], (b, t, kh, d))
        vc = jax.random.normal(ks[2], (b, t, kh, d))
        valid = jnp.broadcast_to(jnp.arange(t)[None, :] < 20, (b, t))
        want = decode_attention(q, kc, vc, valid=valid)
        with use_sharding(mesh):
            got = jax.jit(lambda *a: seq_sharded_decode(
                a[0], a[1], a[2], valid=a[3], axis='model'))(q, kc, vc,
                                                             valid)
        err = float(jnp.max(jnp.abs(got - want)))
        assert err < 1e-5, err
        print('kvseq over model ok:', err)
    """, devices=8)
