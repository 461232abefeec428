"""The granite-4.0-h-micro cell's pieces on the CPU: the configuration file
against the registry, the yardstick's counts against the program's shapes
and the kernel counts, the plain reference against the program in float32,
and a sound run of the training job at a small size."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench_testing import BENCH, TRAIN_SMALL, TRAIN_SMALL_LIMITS, cell
from chipbench import flops, harness, lmdata, weights

CONFIG = harness.load_json(BENCH / "configs" / "granite-4.0-h-micro.L10.json")

# the L10 cut at small widths: the same 10-layer period and multipliers
GRANITE_SMALL = dict(
    CONFIG, name="granite-small", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128,
    shared_intermediate_size=128, vocab_size=256, mamba_d_state=16,
    mamba_d_head=16, mamba_n_heads=8, mamba_chunk_size=16,
    attention_multiplier=0.0625,
    program={
        "registry": "granite-4.0-h-micro",
        "set": {"n_layers": 10, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                "attention_scale": 0.0625, "attention_chunk": 16},
        "ssm": {"d_state": 16, "head_dim": 16, "chunk_size": 16},
        "check": {k: v for k, v in CONFIG["program"]["check"].items()
                  if k != "head_dim"},
    })


def yardstick():
    return harness.load_module(BENCH / "models" / "granitemoehybrid.py")


def test_configuration_is_one_published_period():
    spec = harness.load_json(BENCH.parents[1] / "BENCHMARK.json")
    entry = {c["name"]: c for c in spec["configs"]}["granite-4.0-h-micro.L10"]
    assert CONFIG["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    kinds = CONFIG["layer_types"]
    assert len(kinds) == 40 and CONFIG["num_hidden_layers"] == 10
    assert CONFIG["program"]["check"]["layer_types"] == kinds[:10]
    assert kinds[:10] * 4 == kinds
    assert (CONFIG["mamba_expand"] * CONFIG["hidden_size"]
            == CONFIG["mamba_n_heads"] * CONFIG["mamba_d_head"])
    assert CONFIG["shared_intermediate_size"] == CONFIG["intermediate_size"]
    mc = harness.program_config(CONFIG)
    assert mc.layer_types == kinds[:10] and mc.n_layers == 10


def test_yardstick_counts_the_programs_parameters():
    from repro.models.model import Model
    shapes = jax.eval_shape(Model(harness.program_config(CONFIG)).init,
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == yardstick().param_count(CONFIG) == 951_991_232


def test_yardstick_counts_by_layer_kind():
    m = yardstick()
    # 9 Mamba layers (mixer 54.86M + MLP 100.66M), 1 attention layer
    # (projections 20.97M, causal scores at S=4096 16.78M, MLP 100.66M),
    # the tied head 411.04M
    assert m.fwd_flops_per_token(CONFIG, 4096) == 1_949_166_720
    assert m.train_flops_per_token(CONFIG, 4096) == 3 * 1_949_166_720
    ssd_ops, ssd_bytes = flops.ssd_train(2, 4096, 64, 64, 1, 128, 256, 2, 2)
    fa_ops, fa_bytes = flops.flash_attention_train(2, 4096, 32, 8, 64)
    assert m.train_kernels(CONFIG, 2, 4096) == {
        "ssd": (9 * ssd_ops, 9 * ssd_bytes),
        "flash_attention": (fa_ops, fa_bytes)}


def test_reference_matches_program_in_float32():
    from repro.models.model import Model
    from repro.train.steps import lm_loss_and_metrics

    c = cell(GRANITE_SMALL, {}, {})
    mc = dataclasses.replace(harness.program_config(GRANITE_SMALL),
                             dtype="float32", attention_impl="reference",
                             ssd_impl="reference")
    model = Model(mc)
    params = weights.make(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                          3, jnp.float32)
    rows = lmdata.markov_rows(5, 2, 64, mc.vocab_size, 64)
    ref = c.reference()

    def prog_loss(p):
        return lm_loss_and_metrics(model, p, rows)[1]["loss"]

    def ref_loss(p):
        return ref.loss(p, rows["tokens"], rows["labels"], GRANITE_SMALL)

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(prog_loss))(params)
    lr, gr = jax.jit(jax.value_and_grad(ref_loss))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 1e-4, (weights.path_str(path), err)


def test_sound_granite_run_is_correct():
    """The hybrid cell's job at the small size, through ``SGDRun`` ->
    ``EpochRunner.run_chunk``, against its reference."""
    from chipbench_testing import run_job
    out = run_job(cell(GRANITE_SMALL, TRAIN_SMALL, TRAIN_SMALL_LIMITS))
    assert harness.correct(out["checks"]), out["checks"]
    assert out["failed"] == 0
    assert set(out["facts"]["kernels"]) == {"ssd", "flash_attention"}
