"""granite-4.0-h-micro's hybrid block at smoke widths: the 10-layer period
(Mamba-2 layers around one attention layer at position 5, an MLP in every
layer), no position embedding in attention, the set softmax scale and the
three multipliers; and the plans of the models without a layer pattern
stay as they were."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import OptimizerConfig, ScheduleConfig
from repro.core.schedules import schedule_fn
from repro.models.model import LayerKind, Model
from repro.train.steps import lm_loss_and_metrics, make_lm_train_step

ARCH = "granite-4.0-h-micro"


@pytest.fixture(scope="module")
def granite():
    cfg = registry.get_smoke_config(ARCH)
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _batch(cfg, S=32, seed=1):
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(key, (2, S), 0, cfg.vocab_size)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}


def test_period_puts_attention_at_position_5_and_an_mlp_everywhere(granite):
    cfg, model, params = granite
    assert cfg.layer_pattern == registry.get_config(ARCH).layer_pattern
    assert [k.block for k in model.unit_kinds] == \
        ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    assert model.n_units == 1 and model.tail_kinds == []
    assert all(k.mlp for k in model.unit_kinds if k.block == "mamba")
    assert model.unit_slots[5] == ("attn", 0)
    assert model.unit_slots[6] == ("mamba", 5)
    blocks = params["blocks"]
    assert set(blocks) == {"mamba", "attn"}
    assert set(blocks["mamba"]) == {"ln1", "mamba", "ln2", "mlp"}
    assert set(blocks["attn"]) == {"ln1", "attn", "ln2", "mlp"}
    assert blocks["mamba"]["mlp"]["wi"].shape == (1, 9, cfg.d_model, cfg.d_ff)
    assert blocks["attn"]["mlp"]["wi"].shape == (1, 1, cfg.d_model, cfg.d_ff)
    assert "shared" not in params
    assert model.layer_counts() == {"mamba": 9, "attention": 1}


def test_published_period_repeats_over_40_layers():
    cfg = registry.get_config(ARCH)
    kinds = cfg.layer_types
    assert len(kinds) == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    assert Model(cfg).n_units == 4


def test_a_mixed_period_cut_mid_way_is_refused():
    cfg = dataclasses.replace(registry.get_smoke_config(ARCH), n_layers=7)
    with pytest.raises(ValueError, match="mixed part"):
        Model(cfg)


def test_attention_takes_no_positions(granite):
    """NoPE: the same tokens at other positions (spread three times as far
    apart, so no rotation is merely relative) give the same logits; a
    rotary model's logits move."""
    cfg, model, params = granite
    tokens = _batch(cfg)["tokens"]
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    a, _ = model.apply(params, tokens, positions=pos)
    b, _ = model.apply(params, tokens, positions=3 * pos + 100)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rope = dataclasses.replace(cfg, position_embedding="rope")
    ra, _ = Model(rope).apply(params, tokens, positions=pos)
    rb, _ = Model(rope).apply(params, tokens, positions=3 * pos + 100)
    assert float(jnp.max(jnp.abs(ra - rb))) > 1e-4


def _loss(cfg, params, batch):
    return float(lm_loss_and_metrics(Model(cfg), params, batch)[0])


@pytest.mark.parametrize("field", ["embedding_multiplier",
                                   "residual_multiplier", "logits_scaling",
                                   "attention_scale"])
def test_each_multiplier_moves_the_loss(granite, field):
    """Set to 1 (the softmax scale to 1/sqrt(head_dim)), each one changes
    the loss."""
    cfg, _, params = granite
    batch = _batch(cfg)
    plain = dataclasses.replace(
        cfg, **{field: 0.0 if field == "attention_scale" else 1.0})
    assert abs(_loss(cfg, params, batch) - _loss(plain, params, batch)) > 1e-4


def test_logits_are_divided_by_logits_scaling(granite):
    cfg, model, params = granite
    tokens = _batch(cfg)["tokens"]
    scaled, _ = model.apply(params, tokens)
    plain, _ = Model(dataclasses.replace(cfg, logits_scaling=1.0)).apply(
        params, tokens)
    np.testing.assert_allclose(np.asarray(scaled) * cfg.logits_scaling,
                               np.asarray(plain), rtol=1e-5, atol=1e-6)


def test_train_step_moves_every_leaf(granite):
    cfg, model, params = granite
    opt_init, step = make_lm_train_step(
        model, OptimizerConfig(kind="sgd"),
        schedule_fn(ScheduleConfig(kind="const", peak_lr=0.01)))
    new, _, metrics = jax.jit(step)(params, opt_init(params), _batch(cfg), 0)
    assert bool(jnp.isfinite(metrics["loss"]))
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(new)):
        assert float(jnp.max(jnp.abs(a - b))) > 0, jax.tree_util.keystr(path)


def test_prefill_then_decode_matches_the_full_forward(granite):
    cfg, model, params = granite
    tokens = _batch(cfg, S=27)["tokens"]
    full, _ = model.apply(params, tokens)
    lp, cache = model.prefill(params, tokens[:, :24], cache_len=27)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, 23]),
                               atol=2e-5, rtol=2e-4)
    for t in range(24, 27):
        ld, cache = model.decode(params, cache, tokens[:, t][:, None], t)
        np.testing.assert_allclose(np.asarray(ld), np.asarray(full[:, t]),
                                   atol=2e-5, rtol=2e-4)


def test_zamba2_keeps_its_shared_block_plan():
    cfg = registry.get_smoke_config("zamba2-7b")
    model = Model(cfg)
    assert model.unit_kinds == [LayerKind("mamba")] * cfg.shared_attn_every
    assert model.unit_slots == [(None, 0), (None, 1)]
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(params["blocks"]) == {"ln1", "mamba"}
    assert set(params["shared"]) == {"ln1", "ln2", "attn", "mlp"}
    assert model.layer_counts() == {"mamba": 4, "attention": 2}


# sha256 (first 16 hex digits) of the lowered train step, without debug
# information, of smoke models that have no layer pattern and no
# multipliers, as the model lowered them before the hybrid period existed
UNIFORM_STEPS = {"internlm2-1.8b": "cee5901f91b1c359",
                 "mamba2-2.7b": "09c19b2434a4ed69",
                 "zamba2-7b": "12f888a4d68706af"}


@pytest.mark.parametrize("arch", sorted(UNIFORM_STEPS))
def test_uniform_models_lower_to_the_same_step(arch):
    """A change that alters these steps on purpose records their new
    fingerprints here."""
    cfg = registry.get_smoke_config(arch)
    model = Model(cfg)
    opt_init, step = make_lm_train_step(
        model, OptimizerConfig(kind="sgd"),
        schedule_fn(ScheduleConfig(kind="const", peak_lr=0.01)))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = jax.jit(step).lower(
        params, jax.eval_shape(opt_init, params),
        {"tokens": tok, "labels": tok}, 0).as_text(debug_info=False)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        UNIFORM_STEPS[arch]
