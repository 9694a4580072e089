import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first init). Everything else follows.
import argparse
import dataclasses
import json
import re
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES_BY_NAME, canon, get_config, shapes_for
from repro.launch.mesh import (cache_specs, make_production_mesh, opt_specs,
                               param_specs)
from repro.models import build_model
from repro.models.layers import unbox
from repro.models.model_zoo import Model
from repro.models.transformer import Flags
from repro.models.sharding import use_sharding
from repro.serve.serve_step import make_decode_step, make_prefill_step
from repro.train.optimizer import TrainState, init_opt_state
from repro.train.train_step import TrainConfig, make_train_step

from jax.sharding import NamedSharding, PartitionSpec as PS

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes of every collective op in (post-SPMD) HLO text.
    Shapes in the partitioned module are per-device."""
    out = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for coll in COLLECTIVES:
            # match " = <shape> all-reduce(" etc.; exclude -start/-done pairs
            # counting twice (count only the -start or the plain form)
            token = f" {coll}("
            token_start = f" {coll}-start("
            use = None
            if token in stripped:
                use = stripped.split(token, 1)
            elif token_start in stripped:
                use = stripped.split(token_start, 1)
            if use is None:
                continue
            operands = use[1]
            total = sum(_shape_bytes(m.group(1), m.group(2))
                        for m in _SHAPE_RE.finditer(operands))
            if total == 0:
                # fall back to the result shape on the lhs
                m = _SHAPE_RE.search(use[0])
                if m:
                    total = _shape_bytes(m.group(1), m.group(2))
            out[coll] += total
            break
    return out


def _flags_for(seq_shard: bool) -> Flags:
    """The lowering's model flags: full remat, expert-parallel MoE, and a
    data-sharded KV cache when the decode batch does not divide the data
    axis."""
    return Flags(
        remat="full",
        moe_mode="ep",
        seq_shard_kv="data" if seq_shard else None,
        param_dtype=jnp.bfloat16,
        loss_chunk=1024,
        flash_block=512,
    )


def _rules_for(opt_level: str) -> Dict[str, Any]:
    if opt_level == "opt":
        # beyond-paper: sequence-parallel activations at layer boundaries
        return {"act_seq": "model"}
    return {}


def abstract_boxed(model: Model):
    boxed = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    return unbox(boxed)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               opt_level: str = "baseline") -> Dict[str, Any]:
    """Lower and compile one (arch × shape) step on the production mesh
    and report what the compiled program shows: XLA's cost and memory
    analyses, per-device collective bytes, HLO size, and the model's own
    FLOPs per device."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    if shape not in shapes_for(cfg):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "full-attention arch skips long_500k (see DESIGN)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    seq_shard = (shape.kind == "decode"
                 and shape.global_batch % mesh.shape["data"] != 0)
    flags = _flags_for(seq_shard)
    cache_seq_axis = None
    if opt_level == "opt" and shape.kind == "decode" and not seq_shard \
            and cfg.n_kv_heads % mesh.shape.get("model", 1) != 0 \
            and not cfg.attention_free:
        # kv-head-replicated GQA: shard the KV cache's sequence instead
        flags = dataclasses.replace(flags, seq_shard_kv="model")
        cache_seq_axis = "model"
    rules = _rules_for(opt_level)
    model = build_model(cfg, flags)

    def input_shardings(in_specs):
        """Shard dim 0 (batch) over the widest dividing data-axis group."""
        def for_one(v):
            parts: list = [None] * v.ndim
            cands = [tuple(a for a in ("pod", "data") if a in mesh.shape),
                     ("data",)]
            for axes_ in cands:
                size = int(np.prod([mesh.shape[a] for a in axes_]))
                if v.shape[0] % size == 0:
                    parts[0] = axes_ if len(axes_) > 1 else axes_[0]
                    break
            return NamedSharding(mesh, PS(*parts))
        return {k: for_one(v) for k, v in in_specs.items()}

    t0 = time.time()
    with use_sharding(mesh, rules):
        params_abs, axes = abstract_boxed(model)
        in_specs = model.input_specs(shape)
        batch_shardings = input_shardings(in_specs)
        if shape.kind == "train":
            state_abs = jax.eval_shape(
                lambda p: TrainState(params=p, opt=init_opt_state(p)),
                params_abs)
            state_spec = opt_specs(state_abs, axes, mesh)
            step = make_train_step(model, TrainConfig())
            jitted = jax.jit(step,
                             in_shardings=(state_spec, batch_shardings),
                             out_shardings=(state_spec, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, in_specs)
        elif shape.kind == "prefill":
            pspec = param_specs(params_abs, axes, mesh)
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cspec = cache_specs(cache_abs, mesh, cfg, seq_shard=False)
            step = make_prefill_step(model)
            jitted = jax.jit(step,
                             in_shardings=(pspec, batch_shardings, cspec),
                             out_shardings=(None, None, cspec, None),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_abs, in_specs, cache_abs)
        else:  # decode
            pspec = param_specs(params_abs, axes, mesh)
            cache_abs = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cspec = cache_specs(cache_abs, mesh, cfg, seq_shard=seq_shard,
                                seq_axis=cache_seq_axis)
            step = make_decode_step(model)
            jitted = jax.jit(step,
                             in_shardings=(pspec, cspec,
                                           batch_shardings["tokens"],
                                           batch_shardings["lengths"]),
                             out_shardings=(None, None, cspec, None),
                             donate_argnums=(1,))
            lowered = jitted.lower(
                params_abs, cache_abs, in_specs["tokens"],
                in_specs["lengths"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "chips": n_chips,
        "opt_level": opt_level, "seq_shard_kv": seq_shard,
        "n_layers": cfg.n_layers, "period": len(cfg.layer_pattern),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        result["flops_per_device"] = float(ca.get("flops", -1))
        result["bytes_per_device"] = float(ca.get("bytes accessed", -1))
    except Exception as e:  # pragma: no cover
        result["cost_analysis_error"] = str(e)
    try:
        ma = compiled.memory_analysis()
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                result[attr] = int(v)
    except Exception as e:  # pragma: no cover
        result["memory_analysis_error"] = str(e)
    try:
        hlo = compiled.as_text()
        coll = parse_collective_bytes(hlo)
        result["collective_bytes_per_device"] = coll
        result["collective_total_bytes"] = int(sum(coll.values()))
        result["hlo_lines"] = hlo.count("\n")
    except Exception as e:  # pragma: no cover
        result["hlo_error"] = str(e)

    flops = result.get("flops_per_device", 0.0)
    # model flops: 6·N_active·D(train) / 2·N·D(inference fwd)
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    result["model_flops_per_device"] = mult * n_active * tokens / n_chips
    if flops > 0:
        result["model_vs_hlo_flops"] = result["model_flops_per_device"] / flops
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--opt-level", default="baseline",
                    choices=["baseline", "opt"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    res = lower_cell(canon(args.arch), args.shape, multi_pod=args.multi_pod,
                     opt_level=args.opt_level)
    js = json.dumps(res, indent=2, default=str)
    print(js)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(js)


if __name__ == "__main__":
    main()
