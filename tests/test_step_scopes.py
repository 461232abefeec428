"""The train step names its layers: ``jax.named_scope`` puts ``embed``,
``layers``, ``attention`` / ``mlp`` / ``mixer`` / ``moe``, ``lm_head`` and
``optimizer`` into the ``op_name`` metadata of the compiled program, which
is how a device trace's operations are joined to the layer that made
them."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BLOCK_SCOPES = ("attention", "mlp", "mixer", "moe")
SCOPES = BLOCK_SCOPES + ("embed", "lm_head", "optimizer")

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(hlo: str):
    """(name, opcode, op_name) of every instruction in compiled HLO text."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2), op.group(1) if op else ""))
    return out


def scope_parts(op_name: str) -> set:
    """The scopes on an op_name's path: ``transpose(jvp(layers))`` and
    ``jvp(lm_head)`` name ``layers`` and ``lm_head``."""
    return set(re.split(r"[/()]", op_name))


def lm_chunk_hlo(arch: str, sharding=None) -> str:
    """Compiled HLO of the phase engine's one-step chunk for the arch's
    smoke config in bf16, as the benchmark's training cells run it
    (nesterov SGD, 256-token rows). The SSD chunk is 128, a length the
    TPU kernel tiles."""
    import dataclasses

    from repro.configs import registry
    from repro.configs.base import OptimizerConfig, ScheduleConfig
    from repro.core.adapters import LMAdapter
    from repro.core.schedules import schedule_fn
    from repro.data.pipeline import Loader
    from repro.train.loop import EpochRunner, init_train_state

    cfg = dataclasses.replace(registry.get_smoke_config(arch),
                              dtype="bfloat16")
    if cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk_size=128))
    adapter = LMAdapter(cfg, OptimizerConfig(
        kind="sgd", momentum=0.9, nesterov=True, weight_decay=5e-4))
    tokens = np.zeros((8, 256), np.int32)
    runner = EpochRunner(
        adapter.make_train_step(schedule_fn(ScheduleConfig(peak_lr=0.1))),
        Loader({"tokens": tokens, "labels": tokens}, 2), 0.9)

    def init(key):
        bundle = adapter.init(key)
        return init_train_state(bundle, adapter.init_opt(bundle))

    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    worker = jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding)
    return runner.lower_chunk(state, worker, 1).compile().as_text()


def check_scopes(hlo: str, opcodes, unnamed_ok: bool = False) -> None:
    """Every instruction with one of ``opcodes`` sits under one of
    ``SCOPES``, a block's under ``layers`` too, and ``embed``, ``lm_head``
    and ``optimizer`` are present. ``unnamed_ok`` passes over instructions
    that carry no metadata at all: XLA:CPU makes a few dots of its own
    from the SSD reference's einsums."""
    seen, checked = set(), 0
    for name, opcode, op_name in instructions(hlo):
        parts = scope_parts(op_name)
        seen |= parts & set(SCOPES)
        if opcode not in opcodes or (unnamed_ok and not op_name):
            continue
        checked += 1
        assert parts & set(SCOPES), (name, op_name)
        if parts & set(BLOCK_SCOPES):
            assert "layers" in parts, (name, op_name)
    assert checked and {"lm_head", "optimizer", "embed"} <= seen, seen


@pytest.mark.parametrize("arch,block", [("internlm2-1.8b", "attention"),
                                        ("mamba2-2.7b", "mixer"),
                                        ("granite-moe-3b-a800m", "moe")])
def test_cpu_train_step_matmuls_are_scoped(arch, block):
    hlo = lm_chunk_hlo(arch)
    check_scopes(hlo, ("dot",), unnamed_ok=True)
    assert f"/{block}/" in hlo


def test_hybrid_step_counts_its_layers_and_scopes_each_kind():
    """granite-4.0-h-micro's smoke twin of the benchmark's 10-layer cut:
    tracing its step counts 9 Mamba-2 layers and 1 attention layer
    (``model.layers.*``), and its matmuls fall under ``mixer``,
    ``attention`` and ``mlp``."""
    from repro import obs
    obs.reset()
    hlo = lm_chunk_hlo("granite-4.0-h-micro")
    assert obs.counter("model.layers.mamba") == 9
    assert obs.counter("model.layers.attention") == 1
    check_scopes(hlo, ("dot",), unnamed_ok=True)
    for block in ("mixer", "attention", "mlp"):
        assert f"/{block}/" in hlo
