"""The plain references against the program's own model, both in float32
with the program's jnp (non-kernel) paths, at a small size on the CPU:
the loss and every gradient leaf agree to float32 rounding."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testing import INTERNLM2_SMALL, MAMBA2_SMALL, cell
from chipbench import harness, lmdata, weights


@pytest.mark.parametrize("config", [INTERNLM2_SMALL, MAMBA2_SMALL],
                         ids=["internlm2", "mamba2"])
def test_reference_matches_program_in_float32(config):
    from repro.models.model import Model
    from repro.train.steps import lm_loss_and_metrics

    c = cell(config, {}, {})
    mc = dataclasses.replace(harness.program_config(config),
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
        return ref.loss(p, rows["tokens"], rows["labels"], config)

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(prog_loss))(params)
    lr, gr = jax.jit(jax.value_and_grad(ref_loss))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-6)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 1e-4, (weights.path_str(path), err)
