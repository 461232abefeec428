"""Step factories: training, eval, prefill, decode.

All steps are pure functions (params, ...) -> (params, ...) suitable for
jax.jit with explicit in/out shardings; the SWAP controller and the dry-run
both consume them.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig
from repro.dist.sharding import logical_constraint
from repro.models.model import Model
from repro.optim.api import init_optimizer
from repro.train.precision import (
    PrecisionPolicy, make_precision_train_step, resolve_policy,
)


def lm_loss_and_metrics(model: Model, params, batch: Dict):
    """Cross-entropy next-token loss + router aux; metrics incl. accuracy
    (the paper's phase-1 stopping criterion is TRAIN accuracy)."""
    logits, aux = model.apply(
        params, batch["tokens"],
        vision_embeds=batch.get("vision_embeds"),
        frames=batch.get("frames"))
    with jax.named_scope("lm_head"):
        labels = batch["labels"]
        # CE without take_along_axis / full f32 logits: gathers over a
        # vocab-sharded logits tensor force GSPMD all-gathers (§Perf iter 1);
        # the masked reduction keeps every op vocab-shardable.
        logits_f = logits.astype(jnp.float32)
        m = jax.lax.stop_gradient(jnp.max(logits_f, axis=-1, keepdims=True))
        shifted = logits_f - m
        logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        V = logits.shape[-1]
        label_mask = (jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                               logits.ndim - 1)
                      == labels[..., None])
        l_y = jnp.sum(jnp.where(label_mask, shifted, 0.0), axis=-1)
        nll = logz - l_y
        loss = jnp.mean(nll)
        acc = jnp.mean((jnp.argmax(logits_f, axis=-1) == labels)
                       .astype(jnp.float32))
    total = loss + aux
    return total, {"loss": loss, "aux": aux, "accuracy": acc}


def make_lm_train_step(model: Model, opt_cfg: OptimizerConfig,
                       schedule_fn: Callable,
                       policy: Optional[PrecisionPolicy] = None,
                       grad_accum_steps: int = 1):
    """Returns (opt_init, train_step). train_step: (params, opt_state,
    batch, step) -> (params, opt_state, metrics).

    The stateless params-level surface (dry-run AOT lowering, arch smoke
    tests) over the same precision pipeline the adapters use: grads
    unscaled + cast to ``policy.grad_dtype`` before the data-axis psum,
    master f32 optimizer update, optional microbatch accumulation. The
    caller's ``model`` fixes the compute dtype (``ModelConfig.dtype`` —
    built with ``policy.compute_dtype`` for reduced-precision runs, as the
    LM adapter does). The deprecated ``opt_cfg.grad_dtype`` is folded into
    the resolved policy (``resolve_policy``). Dynamic loss scaling is
    stateful and therefore engine-only: drive it through the adapters /
    ``EpochRunner`` (``TrainState.scale``), not this signature."""
    opt_init, opt_update = init_optimizer(opt_cfg)
    policy = policy if policy is not None \
        else resolve_policy("float32", opt_cfg)
    if policy.dynamic:
        raise ValueError(
            "dynamic loss scaling needs the stateful engine step — use "
            "adapter.make_train_step / EpochRunner (TrainState.scale)")

    def loss_with_aux(params, state, batch):
        total, metrics = lm_loss_and_metrics(model, params, batch)
        return total, (metrics, state)

    step5 = make_precision_train_step(
        loss_with_aux, opt_update, schedule_fn, policy=policy,
        grad_accum_steps=grad_accum_steps, cast_inputs=False)
    const_scale = policy.init_scale_state()

    def train_step(params, opt_state, batch, step):
        # pin every batch leaf to the data axis at the step boundary so the
        # loss (and its backward) starts from a batch-sharded layout even if
        # the host fed differently-placed arrays; no-op without a mesh
        batch = {k: logical_constraint(v, ("batch",))
                 for k, v in batch.items()}
        bundle, new_opt, _, metrics = step5(
            {"params": params, "state": {}}, opt_state, batch, step,
            const_scale)
        return bundle["params"], new_opt, metrics

    return opt_init, train_step


def make_lm_eval_fn(model: Model):
    def eval_fn(params, batch):
        _, metrics = lm_loss_and_metrics(model, params, batch)
        return metrics
    return eval_fn


def make_prefill_fn(model: Model, cache_len: int | None = None):
    def prefill(params, batch):
        return model.prefill(
            params, batch["tokens"], cache_len=cache_len,
            vision_embeds=batch.get("vision_embeds"),
            frames=batch.get("frames"))
    return prefill


def make_decode_fn(model: Model):
    def decode(params, cache, token, pos):
        return model.decode(params, cache, token, pos)
    return decode
