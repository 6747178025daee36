"""Builds jitted, sharded train / prefill / decode steps for any
(architecture x shape x mesh) cell — the single entry point used by the
trainer, the server and the dry-run.

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input
(weak-type-correct, shardable, no device allocation) — the dry-run lowers
against these.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.logical import logical_sharding_scope
from repro.distributed.sharding import (
    ShardingRules,
    batch_specs,
    make_rules,
    param_specs,
    tree_specs_from_axes,
)
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.layers import abstract_params
from repro.models.transformer import (
    cache_axes,
    cache_spec,
    forward,
    model_spec,
    train_loss,
)
from repro.optim.adamw import AdamWConfig, AdamWState, apply_updates, init_state


# ---------------------------------------------------------------------------
# Input specs (dry-run stand-ins)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStructs for every input of the step kind of ``shape``."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch: Dict[str, Any] = {"labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.input_mode == "embeds":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = {}
        if cfg.input_mode == "embeds":
            batch["embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        out = {"batch": batch}
        if cfg.has_decode:
            out["cache"] = cache_spec(cfg, b, s)
        return out
    if shape.kind == "decode":
        return {
            "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            "cache": cache_spec(cfg, b, s),
            "index": jax.ShapeDtypeStruct((), jnp.int32),
        }
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


def make_train_step(
    cfg: ModelConfig, opt_cfg: AdamWConfig
) -> Callable[..., Tuple[Any, AdamWState, Dict[str, jax.Array]]]:
    def train_step(params, opt_state, batch):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: train_loss(p, cfg, batch), has_aux=True
        )(params)
        new_params, new_opt, opt_metrics = apply_updates(opt_cfg, params, grads, opt_state)
        metrics = {"loss": loss, **parts, **opt_metrics}
        return new_params, new_opt, metrics

    return train_step


def make_grad_step(cfg: ModelConfig) -> Callable[..., Tuple[Any, Dict[str, jax.Array]]]:
    """Gradient-only step — the grid runtime's microbatch job body."""

    def grad_step(params, batch):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: train_loss(p, cfg, batch), has_aux=True
        )(params)
        return grads, {"loss": loss, **parts}

    return grad_step


def make_prefill_step(cfg: ModelConfig) -> Callable[..., Tuple[jax.Array, Any]]:
    def prefill_step(params, batch, cache):
        logits, new_cache, _ = forward(
            params,
            cfg,
            tokens=batch.get("tokens"),
            embeds=batch.get("embeds"),
            cache=cache,
            cache_index=jnp.asarray(0, jnp.int32),
        )
        return logits[:, -1:, :], new_cache

    return prefill_step


def make_encoder_step(cfg: ModelConfig) -> Callable[..., jax.Array]:
    """Encoder-only forward (hubert prefill cells)."""

    def encoder_step(params, batch):
        logits, _, _ = forward(
            params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds")
        )
        return logits

    return encoder_step


def make_decode_step(cfg: ModelConfig) -> Callable[..., Tuple[jax.Array, Any]]:
    def decode_step(params, tokens, cache, index):
        logits, new_cache, _ = forward(
            params, cfg, tokens=tokens, cache=cache, cache_index=index
        )
        return logits, new_cache

    return decode_step


# ---------------------------------------------------------------------------
# Sharded (jitted) step bundles
# ---------------------------------------------------------------------------


@dataclass
class StepBundle:
    """Everything needed to run/lower one (arch, shape, mesh) cell."""

    cfg: ModelConfig
    shape: ShapeConfig
    mesh: Mesh
    rules: ShardingRules
    fn: Callable  # the jitted step
    in_specs: Tuple[Any, ...]  # ShapeDtypeStructs, in call order
    param_pspecs: Any
    kind: str

    def _spec_fn(self):
        mesh, rules = self.mesh, self.rules

        def spec_fn(shape, axes):
            return NamedSharding(mesh, rules.spec_for(shape, axes))

        return spec_fn

    def lower(self):
        # the logical-constraint scope must be active while jit traces
        with logical_sharding_scope(self._spec_fn()):
            return self.fn.lower(*self.in_specs)

    def __call__(self, *args):
        with logical_sharding_scope(self._spec_fn()):
            return self.fn(*args)


def _sharding(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_step(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    opt_cfg: Optional[AdamWConfig] = None,
    rules_overrides: Optional[Dict[str, Tuple[str, ...]]] = None,
    donate: bool = True,
) -> StepBundle:
    """Construct the jitted sharded step for one cell."""
    rules = make_rules(mesh, rules_overrides)
    spec_tree = model_spec(cfg)
    p_abstract = abstract_params(spec_tree, cfg.param_dtype)
    p_pspecs = param_specs(rules, spec_tree)
    p_shardings = _sharding(mesh, p_pspecs)
    ins = input_specs(cfg, shape)

    if shape.kind == "train":
        opt_cfg = opt_cfg or AdamWConfig()
        step = make_train_step(cfg, opt_cfg)
        opt_abstract = jax.eval_shape(init_state, p_abstract)
        opt_pspecs = AdamWState(count=P(), mu=p_pspecs, nu=p_pspecs)
        opt_shardings = AdamWState(
            count=NamedSharding(mesh, P()),
            mu=_sharding(mesh, p_pspecs),
            nu=_sharding(mesh, p_pspecs),
        )
        b_pspecs = batch_specs(rules, ins["batch"])
        b_shardings = _sharding(mesh, b_pspecs)
        fn = jax.jit(
            step,
            in_shardings=(p_shardings, opt_shardings, b_shardings),
            out_shardings=(p_shardings, opt_shardings, None),
            donate_argnums=(0, 1) if donate else (),
        )
        return StepBundle(
            cfg, shape, mesh, rules, fn,
            (p_abstract, opt_abstract, ins["batch"]), p_pspecs, "train",
        )

    if shape.kind == "prefill":
        b_pspecs = batch_specs(rules, ins["batch"])
        b_shardings = _sharding(mesh, b_pspecs)
        if not cfg.has_decode:
            step = make_encoder_step(cfg)
            fn = jax.jit(
                step,
                in_shardings=(p_shardings, b_shardings),
                out_shardings=None,
            )
            return StepBundle(
                cfg, shape, mesh, rules, fn, (p_abstract, ins["batch"]), p_pspecs, "prefill"
            )
        c_axes = cache_axes(cfg)
        c_pspecs = tree_specs_from_axes(rules, ins["cache"], c_axes)
        c_shardings = _sharding(mesh, c_pspecs)
        step = make_prefill_step(cfg)
        fn = jax.jit(
            step,
            in_shardings=(p_shardings, b_shardings, c_shardings),
            out_shardings=(None, c_shardings),
            donate_argnums=(2,) if donate else (),
        )
        return StepBundle(
            cfg, shape, mesh, rules, fn,
            (p_abstract, ins["batch"], ins["cache"]), p_pspecs, "prefill",
        )

    if shape.kind == "decode":
        c_axes = cache_axes(cfg)
        c_pspecs = tree_specs_from_axes(rules, ins["cache"], c_axes)
        c_shardings = _sharding(mesh, c_pspecs)
        tok_sharding = NamedSharding(
            mesh, rules.spec_for((shape.global_batch, 1), ("batch", None))
        )
        step = make_decode_step(cfg)
        fn = jax.jit(
            step,
            in_shardings=(
                p_shardings,
                tok_sharding,
                c_shardings,
                NamedSharding(mesh, P()),
            ),
            out_shardings=(None, c_shardings),
            donate_argnums=(2,) if donate else (),
        )
        return StepBundle(
            cfg, shape, mesh, rules, fn,
            (p_abstract, ins["tokens"], ins["cache"], ins["index"]),
            p_pspecs, "decode",
        )

    raise ValueError(shape.kind)


def model_flops_for_cell(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS for the roofline table."""
    if shape.kind == "train":
        return cfg.train_flops_per_token() * shape.tokens
    if shape.kind == "prefill":
        per = cfg.train_flops_per_token() / 3.0  # forward only: 2·N
        return per * shape.tokens
    # decode: one token per sequence against a seq_len context
    return cfg.decode_flops_per_token(context=shape.seq_len) * shape.global_batch
