"""Per-kernel shape/dtype sweeps asserting allclose against the ref oracles
(interpret=True executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref  # noqa: E402

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                   (128, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes(m, k, n, dtype):
    a = jax.random.normal(KEY, (m, k), dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n), dtype)
    got = ops.matmul(a, b)
    want = ref.matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(mt=st.integers(1, 3), kt=st.integers(1, 3), nt=st.integers(1, 3))
def test_matmul_property(mt, kt, nt):
    m, k, n = 128 * mt, 128 * kt, 128 * nt
    a = jax.random.normal(KEY, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 7), (k, n), jnp.float32)
    got = ops.matmul(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-3, atol=1e-3)


FACES = ("lo0", "hi0", "lo1", "hi1", "lo2", "hi2")


def _chunk(shape, key, nonzero=("u",) + FACES):
    """The chunk ``u`` and its six faces, uniform in [0, 1) where named in
    ``nonzero`` and zero elsewhere."""
    x, y, z = shape
    shapes = dict(u=shape, lo0=(y, z), hi0=(y, z), lo1=(x, z), hi1=(x, z),
                  lo2=(x, y), hi2=(x, y))
    return [jax.random.uniform(jax.random.fold_in(key, i), shapes[name])
            if name in nonzero else jnp.zeros(shapes[name], jnp.float32)
            for i, name in enumerate(("u",) + FACES)]


@pytest.mark.parametrize("shape,bx", [((8, 8, 128), 8), ((16, 16, 256), 8),
                                      ((20, 264, 128), 8)])
def test_jacobi3d_kernel(shape, bx):
    """The face-fed kernel against the jnp stencil with every face non-zero;
    (20, 264, 128) ends in a partial block of 4 planes and sweeps each plane
    in three tiles of rows."""
    from repro.apps.jacobi3d import stencil_jnp
    args = _chunk(shape, KEY)
    got = ops.jacobi3d(*args, bx=bx)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(stencil_jnp(*args)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("face", FACES)
def test_jacobi3d_kernel_one_face(face):
    """Each face alone, on a zero chunk: its values land on its own boundary
    plane only."""
    from repro.apps.jacobi3d import stencil_jnp
    args = _chunk((16, 16, 256), KEY, nonzero=(face,))
    got = np.asarray(ops.jacobi3d(*args))
    want = np.asarray(stencil_jnp(*args))
    assert np.count_nonzero(want) == args[1 + FACES.index(face)].size
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape,kernel", [((16, 16, 256), True),
                                          ((10, 34, 6), False),
                                          ((8, 1024, 1024), False)])
def test_stencil_update_picks_its_path_by_shape(shape, kernel):
    """stencil_update carries the Pallas stencil (for a TPU) only for
    chunks whose rows fill whole (8, 128) tiles and whose blocks of 8 planes
    fit VMEM; an unaligned chunk or a 1024² plane runs the jnp body, and
    both match a plain numpy sweep here."""
    from repro.apps.jacobi3d import stencil_update
    args = _chunk(shape, KEY)
    assert ("pallas_call" in str(jax.make_jaxpr(stencil_update)(*args))
            ) == kernel
    u, lo0, hi0, lo1, hi1, lo2, hi2 = (np.asarray(a, np.float64)
                                       for a in args)
    p = np.pad(u, 1)
    p[0, 1:-1, 1:-1], p[-1, 1:-1, 1:-1] = lo0, hi0
    p[1:-1, 0, 1:-1], p[1:-1, -1, 1:-1] = lo1, hi1
    p[1:-1, 1:-1, 0], p[1:-1, 1:-1, -1] = lo2, hi2
    want = (p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1] + p[1:-1, :-2, 1:-1] +
            p[1:-1, 2:, 1:-1] + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:]) / 6
    np.testing.assert_allclose(np.asarray(stencil_update(*args)), want,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bc,q,h,p,n", [(2, 16, 4, 8, 16), (1, 32, 2, 16, 8),
                                        (4, 8, 8, 4, 4)])
def test_ssd_chunk_kernel(bc, q, h, p, n):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (bc, q, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bc, q, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (bc, q, n))
    C = jax.random.normal(ks[4], (bc, q, n))
    gy, gs = ops.ssd_chunk(x, dt, A, B, C)
    wy, ws = ref.ssd_chunk_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(gy), np.asarray(wy), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(ws), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("s,t,d,causal", [(256, 256, 64, True),
                                          (128, 256, 64, False),
                                          (256, 128, 32, False)])
def test_flash_kernel(s, t, d, causal):
    if causal:
        t = s
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (4, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (4, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (4, t, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal)
    want = ref.flash_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_model_flash_matches_pallas():
    """The scan-based model attention and the Pallas kernel agree."""
    from repro.models.attention import flash_attention as model_flash
    ks = jax.random.split(KEY, 3)
    b, s, kh, g, d = 2, 256, 2, 2, 32
    q = jax.random.normal(ks[0], (b, s, kh, g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    got = model_flash(q, k, v, q_positions=jnp.arange(s),
                      kv_positions=jnp.arange(s), causal=True, q_block=128,
                      kv_block=128)
    # fold to [B*KH*G, S, D] with GQA broadcast for the kernel
    qf = q.transpose(0, 2, 3, 1, 4).reshape(b * kh * g, s, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3)[:, :, None], g, 2
                    ).reshape(b * kh * g, s, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3)[:, :, None], g, 2
                    ).reshape(b * kh * g, s, d)
    want = ops.flash_attention(qf, kf, vf, causal=True)
    want = want.reshape(b, kh, g, s, d).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
