"""Jitted distributed step functions: train (grad-accumulated, compressed,
donated), prefill and decode — the functions the dry-run lowers and the
launchers execute.

All sharding is derived from :mod:`repro.runtime.sharding` rules; the same
builders serve a single CPU device (tests), the 16x16 single-pod mesh and
the 2x16x16 multi-pod mesh (dry-run / production).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.transformer import (
    decode_step as _decode_step,
    init_model,
    loss_fn,
    prefill as _prefill,
)
from repro.optim import OptimizerConfig, adamw_update, init_opt_state
from .compression import compressed_grad_fn
from .sharding import (
    axis_rules,
    batch_shardings,
    cache_shardings,
    param_shardings,
    replicated,
)


@dataclass(frozen=True)
class TrainRunConfig:
    optimizer: OptimizerConfig = OptimizerConfig()
    num_microbatches: int = 1
    grad_compression: str = "none"  # none | int8-pod
    accum_dtype: str = "float32"  # gradient accumulation dtype


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------
def init_train_state(key, cfg: ModelConfig, run: TrainRunConfig):
    params = init_model(key, cfg)
    return {"params": params, "opt": init_opt_state(params, run.optimizer)}


def abstract_train_state(cfg: ModelConfig, run: TrainRunConfig, seed: int = 0):
    return jax.eval_shape(
        lambda k: init_train_state(k, cfg, run), jax.random.key(seed)
    )


def train_state_shardings(state, mesh: Mesh):
    return {
        "params": param_shardings(state["params"], mesh),
        "opt": {
            "m": param_shardings(state["opt"]["m"], mesh),
            "v": param_shardings(state["opt"]["v"], mesh),
            "step": replicated(mesh),
        },
    }


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def _split_microbatches(batch, n_mb: int):
    def resh(x):
        b = x.shape[0]
        if b % n_mb:
            raise ValueError(f"global batch {b} not divisible by microbatches {n_mb}")
        return x.reshape(n_mb, b // n_mb, *x.shape[1:])

    return jax.tree.map(resh, batch)


def make_train_step(cfg: ModelConfig, run: TrainRunConfig, mesh: Mesh | None = None):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def grad_fn(params, mb):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, mb, cfg
        )
        return dict(metrics, loss=loss), grads

    def accumulate(params, batch):
        if run.num_microbatches <= 1:
            return grad_fn(params, batch)
        mbs = _split_microbatches(batch, run.num_microbatches)
        acc_dt = jnp.dtype(run.accum_dtype)

        def body(carry, mb):
            g_acc, m_acc = carry
            metrics, grads = grad_fn(params, mb)
            g_acc = jax.tree.map(lambda a, g: a + g.astype(acc_dt), g_acc, grads)
            m_acc = jax.tree.map(lambda a, m: a + m, m_acc, metrics)
            return (g_acc, m_acc), None

        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dt), params
        )
        m0 = {
            "loss": jnp.zeros((), jnp.float32),
            "ce": jnp.zeros((), jnp.float32),
            "aux": jnp.zeros((), jnp.float32),
            "ppl": jnp.zeros((), jnp.float32),
        }
        (g_acc, m_acc), _ = jax.lax.scan(body, (g0, m0), mbs)
        inv = 1.0 / run.num_microbatches
        grads = jax.tree.map(lambda g, p: (g * inv).astype(p.dtype), g_acc, params)
        metrics = jax.tree.map(lambda m: m * inv, m_acc)
        return metrics, grads

    reducer = accumulate
    if run.grad_compression == "int8-pod" and mesh is not None:
        reducer = compressed_grad_fn(accumulate, mesh, None)

    def train_step(state, batch):
        metrics, grads = reducer(state["params"], batch)
        params, opt, opt_metrics = adamw_update(
            state["params"], grads, state["opt"], run.optimizer
        )
        metrics.update(opt_metrics)
        return {"params": params, "opt": opt}, metrics

    return train_step


def lower_train_step(
    cfg: ModelConfig,
    run: TrainRunConfig,
    mesh: Mesh,
    batch_spec: dict,
):
    """Shard + lower the train step on ``mesh`` (dry-run and launcher path)."""
    state = abstract_train_state(cfg, run)
    state_sh = train_state_shardings(state, mesh)
    batch_sh = batch_shardings(batch_spec, mesh)
    step = make_train_step(cfg, run, mesh)

    def wrapped(state, batch):
        with axis_rules(mesh):
            return step(state, batch)

    jitted = jax.jit(
        wrapped,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, replicated(mesh)),
        donate_argnums=(0,),
    )
    with jax.set_mesh(mesh):
        lowered = jitted.lower(state, batch_spec)
    return jitted, lowered, (state, state_sh, batch_sh)


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig, max_len: int, mesh: Mesh | None = None):
    def prefill_step(params, batch):
        with axis_rules(mesh) if mesh is not None else _null():
            return _prefill(params, batch, cfg, max_len)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh: Mesh | None = None):
    def decode(params, tokens, cache, index):
        with axis_rules(mesh) if mesh is not None else _null():
            return _decode_step(params, tokens, cache, index, cfg)

    return decode


def lower_prefill_step(cfg: ModelConfig, mesh: Mesh, batch_spec: dict, max_len: int):
    from repro.models.transformer import abstract_params

    params = abstract_params(cfg)
    params_sh = param_shardings(params, mesh)
    batch_sh = batch_shardings(batch_spec, mesh)
    step = make_prefill_step(cfg, max_len, mesh)
    jitted = jax.jit(step, in_shardings=(params_sh, batch_sh))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params, batch_spec)
    return jitted, lowered, (params, params_sh)


def lower_decode_step(
    cfg: ModelConfig, mesh: Mesh, batch_spec: dict, cache_spec,
    quantized: bool = False,
):
    from repro.models.transformer import abstract_params

    params = abstract_params(cfg)
    if quantized:  # W4A8 packed-weight serving artifact (§Perf-3)
        from repro.quant.serve_packed import pack_decode_params
        from .sharding import SERVING_QUANT_RULES

        params = jax.eval_shape(lambda p: pack_decode_params(p, cfg), params)
        params_sh = param_shardings(params, mesh, SERVING_QUANT_RULES)
    else:
        params_sh = param_shardings(params, mesh)
    tokens_spec = batch_spec["tokens"]
    tokens_sh = batch_shardings({"tokens": tokens_spec}, mesh)["tokens"]
    cache_sh = cache_shardings(cache_spec, cfg, mesh)
    index_spec = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_decode_step(cfg, mesh)
    from .sharding import DEFAULT_RULES, resolve_spec

    b = tokens_spec.shape[0]
    logits_shape = (b, 1, cfg.vocab)
    logits_sh = NamedSharding(
        mesh, resolve_spec(logits_shape, ("batch", None, "vocab"), mesh, DEFAULT_RULES)
    )
    jitted = jax.jit(
        step,
        in_shardings=(params_sh, tokens_sh, cache_sh, replicated(mesh)),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(2,),
    )
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params, tokens_spec, cache_spec, index_spec)
    return jitted, lowered, (params, params_sh, cache_sh)


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
