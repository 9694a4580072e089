"""Decoder-only LM assembly with scan-over-period-blocks.

The layer stack is described by ``cfg.layer_pattern`` (a repeating "period",
e.g. 5×local_attn + 1×global_attn for gemma3). Parameters for the repeated
periods are stacked along a leading ``layers`` axis and the stack is executed
with ``jax.lax.scan`` — this keeps the HLO size O(period) instead of
O(n_layers), which matters both for compile time and for remat policy
uniformity. Remainder layers (n_layers % period) are unrolled at the top of
the stack.

Caches (KV / conv / recurrent state) are threaded through the scan as
per-period xs/ys with the same stacking.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import GLOBAL_ATTN, LOCAL_ATTN, RGLRU, SSD, ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.sharding import constrain


@dataclasses.dataclass(frozen=True)
class Flags:
    """Runtime/lowering flags — the optimization levers for §Perf."""
    remat: str = "dots"              # none | full | dots
    moe_mode: str = "ep"             # ep | dense
    seq_shard_kv: Optional[str] = None   # mesh axis for seq-sharded decode KV
    scan_layers: bool = True
    param_dtype: Any = jnp.bfloat16
    loss_chunk: int = 1024           # seq chunk for the CE loss
    flash_block: int = 512
    use_pallas_flash: bool = False   # Pallas kernel for global attention
                                     # (TPU; interpret mode on CPU)


DEFAULT_FLAGS = Flags()
SMOKE_FLAGS = Flags(remat="none", moe_mode="dense", scan_layers=True,
                    param_dtype=jnp.float32, loss_chunk=64, flash_block=128)


# ---------------------------------------------------------------------------
# Per-layer block = temporal mixer + (MLP | MoE), pre-norm residual
# ---------------------------------------------------------------------------

def _is_moe_layer(cfg: ModelConfig, kind: str) -> bool:
    return cfg.moe is not None and kind in (GLOBAL_ATTN, LOCAL_ATTN) \
        and cfg.moe.interleave == 1


def block_init(key, cfg: ModelConfig, kind: str, dtype) -> Dict:
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"norm1": L.scale_init(cfg.d_model)}
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        p["attn"] = A.attn_init(ks[0], cfg.d_model, cfg.n_heads,
                                cfg.n_kv_heads, cfg.resolved_head_dim, dtype)
    elif kind == SSD:
        p["ssd"] = S.ssd_init(ks[0], cfg.d_model, cfg.ssm, dtype)
    elif kind == RGLRU:
        p["rglru"] = R.rglru_init(ks[0], cfg.d_model, cfg.rglru,
                                  cfg.n_heads, dtype)
    else:
        raise ValueError(kind)
    if kind == SSD:
        return p  # mamba2 blocks have no separate MLP
    p["norm2"] = L.scale_init(cfg.d_model)
    if _is_moe_layer(cfg, kind):
        p["moe"] = M.moe_init(ks[1], cfg.d_model, cfg.moe, cfg.gated_mlp, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def block_apply(p, x: jax.Array, *, cfg: ModelConfig, kind: str, mode: str,
                flags: Flags, cache: Optional[Dict] = None,
                lengths: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind in (GLOBAL_ATTN, LOCAL_ATTN):
        mix, new_cache = A.attention_layer(
            p["attn"], h, kind=kind, window=cfg.window,
            rope_theta=cfg.rope_theta, n_kv_heads=cfg.n_kv_heads, mode=mode,
            lengths=lengths, cache=cache,
            seq_shard_axis=flags.seq_shard_kv,
            use_pallas=flags.use_pallas_flash)
    elif kind == SSD:
        mix, new_cache = S.ssd_layer(p["ssd"], h, scfg=cfg.ssm, mode=mode,
                                     cache=cache)
    elif kind == RGLRU:
        mix, new_cache = R.rglru_layer(p["rglru"], h, rcfg=cfg.rglru,
                                       mode=mode, cache=cache)
    else:
        raise ValueError(kind)
    x = x + mix
    if kind == SSD:
        return x, new_cache, aux
    h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
    if "moe" in p:
        if flags.moe_mode == "ep":
            y, aux = M.moe_ep(p["moe"], h, cfg.moe, cfg.gated_mlp)
        else:
            y, aux = M.moe_dense(p["moe"], h, cfg.moe, cfg.gated_mlp)
    else:
        y = L.mlp_apply(p["mlp"], h, cfg.gated_mlp)
    return x + y, new_cache, aux


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                     dtype) -> Optional[Dict]:
    if kind == GLOBAL_ATTN:
        return A.init_attn_cache(batch, cache_len, cfg.n_kv_heads,
                                 cfg.resolved_head_dim, dtype)
    if kind == LOCAL_ATTN:
        return A.init_attn_cache(batch, min(cfg.window, cache_len),
                                 cfg.n_kv_heads, cfg.resolved_head_dim, dtype)
    if kind == SSD:
        return S.init_ssd_cache(batch, cfg.d_model, cfg.ssm, dtype)
    if kind == RGLRU:
        return R.init_rglru_cache(batch, cfg.d_model, cfg.rglru, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Whole-model init / apply
# ---------------------------------------------------------------------------

def _period_layout(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...]]:
    period = len(cfg.layer_pattern)
    n_periods = cfg.n_layers // period
    remainder = tuple(cfg.layer_pattern[:cfg.n_layers % period])
    return n_periods, remainder


def lm_init(key, cfg: ModelConfig, flags: Flags = DEFAULT_FLAGS):
    dtype = flags.param_dtype
    n_periods, remainder = _period_layout(cfg)
    keys = jax.random.split(key, 4 + len(remainder))
    params: Dict[str, Any] = {
        "embed": L.embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": L.scale_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(keys[1], cfg.d_model, cfg.vocab,
                                         ("embed", "vocab"), dtype)

    def one_period(k):
        ks = jax.random.split(k, len(cfg.layer_pattern))
        return tuple(block_init(ki, cfg, kind, dtype)
                     for ki, kind in zip(ks, cfg.layer_pattern))

    if n_periods:
        pkeys = jax.random.split(keys[2], n_periods)
        stacked = jax.vmap(one_period)(pkeys)
        # prepend the stacking axis to every leaf's logical axes
        stacked = jax.tree.map(
            lambda b: L.Boxed(b.value, ("layers",) + tuple(b.axes)),
            stacked, is_leaf=lambda x: isinstance(x, L.Boxed))
        params["periods"] = stacked
    for i, kind in enumerate(remainder):
        params[f"rem_{i}"] = block_init(keys[4 + i], cfg, kind, dtype)
    return params


def lm_init_cache(cfg: ModelConfig, batch: int, cache_len: int,
                  flags: Flags = DEFAULT_FLAGS):
    dtype = flags.param_dtype
    n_periods, remainder = _period_layout(cfg)
    cache: Dict[str, Any] = {}
    if n_periods:
        def one_period(_):
            return tuple(init_block_cache(cfg, kind, batch, cache_len, dtype)
                         for kind in cfg.layer_pattern)
        cache["periods"] = jax.vmap(one_period)(jnp.arange(n_periods))
    for i, kind in enumerate(remainder):
        cache[f"rem_{i}"] = init_block_cache(cfg, kind, batch, cache_len, dtype)
    return cache


def _embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                  flags: Flags) -> jax.Array:
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.frontend == "vision" and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(x.dtype)       # [B, n_tok, D]
        x = jax.lax.dynamic_update_slice(x, ve, (0, 0, 0))
    x = constrain(x, "act_batch", "act_seq", "act_embed")
    return x


def lm_apply(params, batch: Dict[str, jax.Array], *, cfg: ModelConfig,
             mode: str, flags: Flags = DEFAULT_FLAGS,
             cache: Optional[Dict] = None
             ) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (final hidden [B,S,D], new_cache, aux_loss). The unembedding
    is applied by the caller (train uses a chunked fused CE; serve samples)."""
    n_periods, remainder = _period_layout(cfg)
    lengths = batch.get("lengths")
    x = _embed_inputs(params, cfg, batch, flags)
    aux_total = jnp.zeros((), jnp.float32)

    def period_body(x, period_params, period_cache):
        aux_p = jnp.zeros((), jnp.float32)
        new_caches: List[Any] = []
        for j, kind in enumerate(cfg.layer_pattern):
            c_in = period_cache[j] if period_cache is not None else None
            x, c_out, aux = block_apply(
                period_params[j], x, cfg=cfg, kind=kind, mode=mode,
                flags=flags, cache=c_in, lengths=lengths)
            new_caches.append(c_out)
            aux_p = aux_p + aux
        return x, tuple(new_caches), aux_p

    if flags.remat == "full":
        period_body = jax.checkpoint(period_body)
    elif flags.remat == "dots":
        period_body = jax.checkpoint(
            period_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    if n_periods:
        if mode == "train":
            def scan_fn(carry, pp):
                x, aux = carry
                x, _, aux_p = period_body(x, pp, None)
                return (x, aux + aux_p), None
            (x, aux_total), _ = jax.lax.scan(
                scan_fn, (x, aux_total), params["periods"])
            new_cache = None
        else:
            def scan_fn(carry, inp):
                x, aux = carry
                pp, pc = inp
                x, new_c, aux_p = period_body(x, pp, pc)
                return (x, aux + aux_p), new_c
            (x, aux_total), new_period_cache = jax.lax.scan(
                scan_fn, (x, aux_total), (params["periods"], cache["periods"]))
            new_cache = {"periods": new_period_cache}
    else:
        new_cache = {} if mode != "train" else None

    for i, kind in enumerate(remainder):
        c_in = cache.get(f"rem_{i}") if cache is not None else None
        x, c_out, aux = block_apply(params[f"rem_{i}"], x, cfg=cfg, kind=kind,
                                    mode=mode, flags=flags, cache=c_in,
                                    lengths=lengths)
        aux_total = aux_total + aux
        if new_cache is not None:
            new_cache[f"rem_{i}"] = c_out

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_cache, aux_total


def unembed(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Logits for a (small) x — decode path. [B,S,D] -> [B,S,V]."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    return constrain(logits, "act_batch", None, "act_vocab")


def chunked_ce_loss(params, x: jax.Array, labels: jax.Array,
                    cfg: ModelConfig, flags: Flags) -> jax.Array:
    """Cross-entropy without materializing [B,S,V]: scan over seq chunks,
    vocab-sharded logsumexp. x: [B,S,D], labels: [B,S]."""
    b, s, d = x.shape
    chunk = min(flags.loss_chunk, s)
    assert s % chunk == 0
    n = s // chunk
    xc = jnp.moveaxis(x.reshape(b, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, chunk), 1, 0)

    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]

    def body(total, inp):
        xb, lb = inp                                   # [B,chunk,D], [B,chunk]
        logits = jnp.einsum("btd,dv->btv", xb, w).astype(jnp.float32)
        logits = constrain(logits, "act_batch", None, "act_vocab")
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        ll = jnp.sum(jnp.where(iota == lb[..., None], logits, 0.0), axis=-1)
        return total + jnp.sum(logz - ll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (b * s)
