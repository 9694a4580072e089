"""Property tests of the model substrate's mathematical invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.models import attention as A  # noqa: E402
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import rglru as R
from repro.configs.base import RGLRUConfig, SSMConfig

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, mask):
    """q: [B,S,K,G,D]; k,v: [B,T,K,D]; mask: [S,T] bool."""
    sc = jnp.einsum("bskgd,btkd->bskgt", q, k) * (q.shape[-1] ** -0.5)
    sc = jnp.where(mask[None, :, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bskgt,btkd->bskgd", p, v)


@settings(max_examples=8, deadline=None)
@given(s=st.sampled_from([32, 64, 96]), w=st.sampled_from([8, 16, 32]))
def test_window_attention_equals_masked_full(s, w):
    b, kh, g, d = 2, 2, 2, 8
    ks = jax.random.split(jax.random.fold_in(KEY, s * 100 + w), 3)
    q = jax.random.normal(ks[0], (b, s, kh, g, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    pos = jnp.arange(s)
    got = A.window_attention(q, k, v, positions=pos, window=w)
    mask = (pos[None, :] <= pos[:, None]) & (pos[:, None] - pos[None, :] < w)
    want = _naive_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(s=st.sampled_from([32, 64]), t=st.sampled_from([32, 64, 128]),
       causal=st.booleans())
def test_flash_equals_naive(s, t, causal):
    if causal:
        t = s
    b, kh, g, d = 2, 2, 1, 8
    ks = jax.random.split(jax.random.fold_in(KEY, s * 1000 + t), 3)
    q = jax.random.normal(ks[0], (b, s, kh, g, d))
    k = jax.random.normal(ks[1], (b, t, kh, d))
    v = jax.random.normal(ks[2], (b, t, kh, d))
    got = A.flash_attention(q, k, v, q_positions=jnp.arange(s),
                            kv_positions=jnp.arange(t), causal=causal,
                            q_block=16, kv_block=16)
    mask = (jnp.arange(t)[None, :] <= jnp.arange(s)[:, None]) if causal \
        else jnp.ones((s, t), bool)
    want = _naive_attention(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_attention_permutation_equivariance_over_batch():
    """Permuting the batch permutes the output (no cross-request leakage)."""
    b, s, kh, g, d = 4, 16, 2, 2, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, kh, g, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    out = A.flash_attention(q, k, v, q_positions=jnp.arange(s),
                            kv_positions=jnp.arange(s), causal=True)
    perm = jnp.array([2, 0, 3, 1])
    out_p = A.flash_attention(q[perm], k[perm], v[perm],
                              q_positions=jnp.arange(s),
                              kv_positions=jnp.arange(s), causal=True)
    np.testing.assert_allclose(np.asarray(out[perm]), np.asarray(out_p),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# SSD vs naive recurrence
# ---------------------------------------------------------------------------

def _naive_ssd(x, dt, Av, B, C):
    """Sequential state recurrence oracle. x: [b,s,h,p]; B,C: [b,s,1,n]."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    st = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        dA = jnp.exp(dt[:, t] * Av[None, :])                     # [b,h]
        st = st * dA[:, :, None, None] + jnp.einsum(
            "bhp,bn,bh->bhpn", x[:, t], B[:, t, 0], dt[:, t])
        ys.append(jnp.einsum("bhpn,bn->bhp", st, C[:, t, 0]))
    return jnp.stack(ys, axis=1), st


@pytest.mark.parametrize("s,chunk", [(16, 4), (24, 8), (32, 32)])
def test_ssd_chunked_equals_naive_recurrence(s, chunk):
    b, h, p, n = 2, 2, 4, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    Av = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (b, s, 1, n))
    C = jax.random.normal(ks[4], (b, s, 1, n))
    got_y, got_st = S._ssd_chunked(x, dt, Av, B, C, chunk)
    want_y, want_st = _naive_ssd(x, dt, Av, B, C)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_st), np.asarray(want_st),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU: associative scan vs sequential loop
# ---------------------------------------------------------------------------

def test_rglru_scan_equals_sequential():
    d, w, s, b = 8, 8, 24, 2
    cfg = RGLRUConfig(lru_width=w, conv_width=4)
    params = jax.tree.map(lambda bx: bx.value,
                          R.rglru_init(KEY, d, cfg, n_blocks=2,
                                       dtype=jnp.float32),
                          is_leaf=lambda x: isinstance(x, L.Boxed))
    u = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, d))
    full, _ = R.rglru_layer(params, u, rcfg=cfg, mode="train")
    # sequential: feed one token at a time through decode
    cache = {"conv": jnp.zeros((b, 3, w)), "state": jnp.zeros((b, w))}
    outs = []
    for t in range(s):
        y, cache = R.rglru_layer(params, u[:, t:t + 1], rcfg=cfg,
                                 mode="decode", cache=cache)
        outs.append(y)
    seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(seq),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# losses / numerics
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(2, 6))
def test_chunked_ce_equals_direct(nchunks):
    from repro.configs import get_smoke_config
    from repro.models.transformer import Flags, chunked_ce_loss
    import dataclasses
    cfg = get_smoke_config("yi_9b")
    b, s, dm = 2, 16 * nchunks, cfg.d_model
    ks = jax.random.split(jax.random.fold_in(KEY, nchunks), 3)
    x = jax.random.normal(ks[0], (b, s, dm))
    w = jax.random.normal(ks[1], (dm, cfg.vocab)) * 0.05
    labels = jax.random.randint(ks[2], (b, s), 0, cfg.vocab)
    params = {"unembed": w}
    flags = Flags(loss_chunk=16, param_dtype=jnp.float32)
    got = chunked_ce_loss(params, x, labels, cfg, flags)
    logits = x @ w
    want = L.softmax_cross_entropy(logits, labels)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 512), st.floats(1e3, 1e6))
def test_rope_preserves_norm(pos, theta):
    x = jax.random.normal(KEY, (1, 1, 2, 16))
    y = L.apply_rope(x, jnp.array([[pos]]), theta)
    np.testing.assert_allclose(float(jnp.linalg.norm(y)),
                               float(jnp.linalg.norm(x)), rtol=1e-4)


def test_rms_norm_scale_equivariance():
    x = jax.random.normal(KEY, (2, 8, 16))
    g = jnp.ones((16,))
    a = L.rms_norm(x, g)
    b = L.rms_norm(x * 42.0, g)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# MoE invariants
# ---------------------------------------------------------------------------

def test_moe_dense_routing_invariants():
    from repro.configs import MoEConfig
    from repro.models import moe as M
    mcfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=16)
    p = jax.tree.map(lambda b: b.value,
                     M.moe_init(KEY, 8, mcfg, True, dtype=jnp.float32),
                     is_leaf=lambda x: isinstance(x, L.Boxed))
    x = jax.random.normal(KEY, (2, 8, 8))
    out, aux = M.moe_dense(p, x, mcfg, True)
    assert out.shape == x.shape
    assert float(aux) >= 0
    w, idx, _ = M._route(p["router"], x.reshape(-1, 8), mcfg)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert int(idx.max()) < mcfg.num_experts


def test_moe_tiny_capacity_drops_gracefully():
    """With capacity_factor→tiny the EP path must drop tokens (finite,
    smaller-magnitude output), never crash. Run inside shard_map on a
    1×1 mesh so _ep_local sees a real axis."""
    from jax.sharding import PartitionSpec as PS
    from repro.configs import MoEConfig
    from repro.models import moe as M
    mcfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=8)
    p = jax.tree.map(lambda b: b.value,
                     M.moe_init(KEY, 8, mcfg, True, dtype=jnp.float32),
                     is_leaf=lambda x: isinstance(x, L.Boxed))
    xf = jax.random.normal(KEY, (16, 8))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))

    def run(cf):
        body = lambda xloc: M._ep_local(p, xloc, mcfg, True, "model", cf)[0]
        return jax.shard_map(body, mesh=mesh, in_specs=PS(),
                             out_specs=PS(), check_vma=False)(xf)

    full = run(8.0)
    tiny = run(0.05)
    assert np.isfinite(np.asarray(tiny)).all()
    assert float(jnp.abs(tiny).sum()) < float(jnp.abs(full).sum())


def test_pallas_flash_flag_matches_scan_path():
    """use_pallas_flash routes global attention through the Pallas kernel —
    same logits as the scan-based path."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import build_smoke
    from repro.models.layers import unbox
    cfg = get_smoke_config("yi_9b")
    m0 = build_smoke(cfg)
    m1 = build_smoke(cfg, use_pallas_flash=True)
    params, _ = unbox(m0.init(KEY))
    batch = {"tokens": jax.random.randint(KEY, (2, 128), 0, cfg.vocab)}
    x0, _, _ = m0.apply(params, dict(batch), mode="train")
    x1, _, _ = m1.apply(params, dict(batch), mode="train")
    np.testing.assert_allclose(np.asarray(x0), np.asarray(x1),
                               rtol=2e-4, atol=2e-4)
