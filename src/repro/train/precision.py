"""Mixed precision + gradient accumulation for the training stack.

SWAP's phase 1 is defined by very large mini-batches; this module supplies
the two levers that make that regime run "as fast as the hardware allows":

  * ``PrecisionPolicy`` — a frozen, hashable description of the numerics of
    one training phase: master-parameter dtype, forward/backward compute
    dtype, the dtype gradients are cast to before the data-axis reduction,
    and (for float16) dynamic loss scaling with inf/nan step skipping.
    Master weights always stay in ``param_dtype`` (float32 by default);
    reduced precision applies to the compute path and the gradient
    reduction only, so the optimizer update — and everything SWAP averages
    in phase 3 — is full precision.
  * ``LossScaleState`` — the tiny pytree of loss-scaling dynamics (current
    scale, growth counter, cumulative skipped-step counter) that the phase
    engine threads through ``TrainState`` and checkpoints alongside the
    model (see ``repro.train.loop`` / ``repro.checkpoint.state``).
  * ``make_precision_train_step`` — wraps a loss function and an optimizer
    update into the engine's step signature

        (bundle, opt_state, batch, step, scale_state)
            -> (bundle, opt_state, scale_state, metrics)

    handling compute-dtype casting, loss scaling, microbatch gradient
    accumulation (an inner ``lax.scan`` over ``grad_accum_steps`` slices of
    the global batch, so phase-1 batches larger than device memory run as
    accumulated microbatches with identical effective batch size), the
    skip-on-overflow update, and the master-weight optimizer step.

Equivalences the tests pin down (``tests/test_precision.py``):
``grad_accum_steps=k`` over microbatches of ``B/k`` matches the fused
batch-``B`` step to FMA tolerance for stateless models (the LM), and the
pure-float32 policy traces the exact pre-precision step graph (no extra
casts or selects), keeping the engine's bitwise python-loop equivalence
intact. Stateful models are NOT fused-equivalent under accumulation:
BatchNorm statistics are computed per microbatch (k sequential
running-stat updates instead of one batch-B statistic) and the CNN's
augmentation seed is per-global-batch — see docs/training.md
§Precision & accumulation.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class LossScaleState(NamedTuple):
    """Loss-scaling dynamics, carried in ``TrainState.scale``.

    Plain-f32 policies carry the trivial state (scale 1, counters 0) so the
    TrainState structure — and therefore checkpoints — is uniform across
    precision configurations.
    """

    scale: Any         # float32 scalar — current loss scale
    growth_count: Any  # int32 — finite steps since the last scale change
    skipped: Any       # int32 — cumulative inf/nan-skipped steps


@dataclass(frozen=True)
class PrecisionPolicy:
    """Numerics of one training phase. Frozen + hashable (jit-static)."""

    name: str = "float32"
    param_dtype: str = "float32"    # master weights (optimizer + averaging)
    compute_dtype: str = "float32"  # forward/backward math
    grad_dtype: str = "float32"     # gradient dtype for the data-axis psum
    loss_scale: float = 1.0         # initial (or fixed) loss scale
    dynamic: bool = False           # dynamic scaling + inf/nan step skip
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 200      # finite steps between scale growths

    @property
    def scaled(self) -> bool:
        return self.dynamic or self.loss_scale != 1.0

    @property
    def casts_compute(self) -> bool:
        return self.compute_dtype != self.param_dtype

    def cast_for_compute(self, tree):
        """Cast floating leaves to the compute dtype (no-op for f32/f32)."""
        if not self.casts_compute:
            return tree
        dt = jnp.dtype(self.compute_dtype)
        return jax.tree_util.tree_map(
            lambda a: a.astype(dt)
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact) else a, tree)

    def init_scale_state(self) -> LossScaleState:
        return LossScaleState(
            scale=jnp.asarray(self.loss_scale, jnp.float32),
            growth_count=jnp.zeros((), jnp.int32),
            skipped=jnp.zeros((), jnp.int32))

    def update_scale(self, st: LossScaleState, finite) -> LossScaleState:
        """Post-step scaling dynamics: back off on overflow, grow after
        ``growth_interval`` consecutive finite steps."""
        grown = st.growth_count + 1 >= self.growth_interval
        scale = jnp.where(
            finite,
            jnp.where(grown, st.scale * self.growth_factor, st.scale),
            st.scale * self.backoff_factor)
        count = jnp.where(finite & ~grown, st.growth_count + 1, 0)
        return LossScaleState(
            scale=scale.astype(jnp.float32),
            growth_count=count.astype(jnp.int32),
            skipped=st.skipped + (1 - finite.astype(jnp.int32)))


F32 = PrecisionPolicy()
BF16 = PrecisionPolicy(name="bfloat16", compute_dtype="bfloat16")
# float16's narrow exponent needs loss scaling; start high, dynamics adapt
F16 = PrecisionPolicy(name="float16", compute_dtype="float16",
                      loss_scale=2.0 ** 15, dynamic=True)

_PRESETS = {
    "": F32, "f32": F32, "float32": F32, "fp32": F32,
    "bf16": BF16, "bfloat16": BF16,
    "f16": F16, "float16": F16, "fp16": F16,
}


def default_scale_state() -> LossScaleState:
    """The trivial (f32) loss-scale state — what plain callers thread."""
    return F32.init_scale_state()


def stack_scale_state(st: LossScaleState, n: int) -> LossScaleState:
    """Broadcast a scale state to a leading worker axis (phase-2 ensembles:
    every worker starts from the same scale, then evolves independently)."""
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), st)


def resolve_policy(name: str, opt_cfg=None) -> PrecisionPolicy:
    """Preset name -> policy, folding in the deprecated
    ``OptimizerConfig.grad_dtype`` alias: a non-f32 grad dtype on the
    optimizer config still parses and lands on the policy (unless the
    preset already sets one), but the cast now happens inside the precision
    step — after unscaling, before the data-axis reduction — instead of as
    a loose post-``value_and_grad`` cast."""
    policy = _PRESETS.get((name or "").lower())
    if policy is None:
        raise ValueError(
            f"unknown precision preset {name!r}; "
            f"expected one of {sorted(k for k in _PRESETS if k)}")
    if (opt_cfg is not None and opt_cfg.grad_dtype != "float32"
            and policy.grad_dtype == "float32"):
        warnings.warn(
            "OptimizerConfig.grad_dtype is deprecated: set "
            "PhaseConfig.precision / PrecisionPolicy.grad_dtype instead "
            "(the value still applies, now inside the precision step)",
            DeprecationWarning, stacklevel=2)
        policy = dataclasses.replace(policy, grad_dtype=opt_cfg.grad_dtype)
    return policy


def split_microbatches(batch, k: int):
    """Reshape every batch leaf ``(B, ...) -> (k, B/k, ...)``; scalar
    leaves (e.g. the per-batch ``aug_seed``) broadcast across microbatches."""
    def split(v):
        v = jnp.asarray(v)
        if v.ndim == 0:
            return jnp.broadcast_to(v, (k,))
        if v.shape[0] % k:
            raise ValueError(
                f"batch dim {v.shape[0]} not divisible by "
                f"grad_accum_steps={k}")
        return v.reshape((k, v.shape[0] // k) + v.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def all_finite(tree):
    """Scalar bool: every inexact leaf of ``tree`` is finite."""
    fin = jnp.asarray(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            fin = fin & jnp.all(jnp.isfinite(leaf))
    return fin


def make_precision_train_step(loss_with_aux: Callable, opt_update: Callable,
                              schedule_fn: Callable,
                              policy: Optional[PrecisionPolicy] = None,
                              grad_accum_steps: int = 1,
                              cast_inputs: bool = True) -> Callable:
    """The engine-facing train step with the full precision pipeline.

    ``loss_with_aux(params, model_state, batch) -> (loss, (metrics,
    new_model_state))`` — the CNN adapter's loss already has this shape;
    stateless losses pass ``{}`` through. ``cast_inputs=False`` skips the
    pre-cast of params/batch for models that already cast per-op from their
    own compute-dtype config (the LM's ``mdot``); the scaling/accumulation/
    skip machinery is identical either way.

    Skip semantics (``policy.dynamic``): when any unscaled gradient leaf is
    non-finite, parameters, optimizer state, and model state keep their
    previous values, the scale backs off, and ``scale_state.skipped``
    increments; ``metrics["skipped"]`` flags the step so the phase engine
    can freeze its accuracy EMA for it.
    """
    policy = policy or F32
    k = int(grad_accum_steps)
    if k < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, got {k}")
    grad_dtype = jnp.dtype(policy.grad_dtype)
    tree_map = jax.tree_util.tree_map

    def train_step(bundle, opt_state, batch, step, scale_state):
        params, mstate = bundle["params"], bundle["state"]
        scale = scale_state.scale

        def scaled_loss(p, st, mb):
            if cast_inputs:
                p, mb = policy.cast_for_compute((p, mb))
            loss, (metrics, new_st) = loss_with_aux(p, st, mb)
            # model state (BN running stats) stays in its master dtypes so
            # the scan carry — and checkpoints — are dtype-stable
            new_st = tree_map(lambda n, o: n.astype(o.dtype), new_st, st)
            if policy.scaled:
                loss = loss * scale.astype(loss.dtype)
            return loss, (metrics, new_st)

        vg = jax.value_and_grad(scaled_loss, has_aux=True)

        if k == 1:
            (_, (metrics, new_mstate)), grads = vg(params, mstate, batch)
        else:
            # zero-seeded carry so ALL k microbatches run through the one
            # scan body — unrolling microbatch 0 to seed the carry would
            # compile a second full fwd+bwd copy into the step
            micro = split_microbatches(batch, k)
            (_, (m_sh, _)), g_sh = jax.eval_shape(
                vg, params, mstate, tree_map(lambda v: v[0], micro))
            zeros = lambda t: tree_map(                       # noqa: E731
                lambda s: jnp.zeros(s.shape, s.dtype), t)

            def body(carry, mb):
                g_acc, m_acc, st = carry
                (_, (m_i, st_i)), g_i = vg(params, st, mb)
                return (tree_map(jnp.add, g_acc, g_i),
                        tree_map(jnp.add, m_acc, m_i), st_i), None

            (grads, msum, new_mstate), _ = jax.lax.scan(
                body, (zeros(g_sh), zeros(m_sh), mstate), micro)
            metrics = tree_map(lambda m: m / k, msum)

        # unscale (and average over microbatches) in one multiply, then cast
        # to the reduction dtype: the data-axis psum of the backward pass
        # happens on these leaves
        if policy.scaled or k > 1:
            inv = (1.0 / k) / scale if policy.scaled else jnp.float32(1.0 / k)
            grads = tree_map(lambda g: (g * inv.astype(g.dtype)), grads)
        if grad_dtype != jnp.float32:
            grads = tree_map(lambda g: g.astype(grad_dtype), grads)

        with jax.named_scope("optimizer"):
            lr = schedule_fn(step)
            new_params, new_opt = opt_update(grads, opt_state, params, lr)
            if policy.dynamic:
                finite = all_finite(grads)
                keep = lambda n, o: jnp.where(finite, n, o)  # noqa: E731
                new_params = tree_map(keep, new_params, params)
                new_opt = tree_map(keep, new_opt, opt_state)
                new_mstate = tree_map(keep, new_mstate, mstate)
                new_scale = policy.update_scale(scale_state, finite)
                metrics = dict(metrics,
                               skipped=1.0 - finite.astype(jnp.float32),
                               loss_scale=scale)
            else:
                new_scale = scale_state
        return ({"params": new_params, "state": new_mstate}, new_opt,
                new_scale, dict(metrics, lr=lr))

    return train_step
