"""The comparison that decides ``correct`` in a training cell.

The program's first three steps, driven through the window's own call,
leave three readings: each step's loss; the first gradient as the optimizer
got it (SGD's momentum buffer after one step is ``g + wd * p``); and the
parameters' change after three steps. The plain reference follows the same
three steps from the same weights and rows. Norms are compared leaf by leaf
(a stacked layer counts as one leaf per layer): the gap between the
program's norm and the reference's, over the larger of the reference's norm
of that leaf and of the median leaf. The worst leaf is the number compared.

Leaves whose reference gradient is under a thousandth of the median leaf's
move by round-off alone and are left out of both norm comparisons.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

EXCLUDE_BELOW = 1e-3     # of the median leaf's reference gradient norm


def _split(path: str, a, lead: int):
    """(name, array) per leaf: stacked layer leaves (``blocks/...``, axes
    (units, unit_len)) split per layer, after ``lead`` worker axes."""
    out = []
    idx = [()]
    for ax in range(lead):
        idx = [i + (w,) for i in idx for w in range(a.shape[ax])]
    for i in idx:
        sub = a[i] if i else a
        tag = "".join(f"<w{w}>" for w in i)
        if path.startswith("blocks/") or path.startswith("tail/"):
            for u in range(sub.shape[0]):
                for j in range(sub.shape[1] if path.startswith("blocks/")
                               else 1):
                    leaf = sub[u, j] if path.startswith("blocks/") else sub[u]
                    out.append((f"{tag}{path}[{u}.{j}]", leaf))
        else:
            out.append((f"{tag}{path}", sub))
    return out


def leaf_norms(tree, lead: int = 0, fn=None, *more) -> dict:
    """{leaf name: L2 norm} in float32, computed on the device; with
    ``fn``, of ``fn(leaf, *matching leaves of more)``, fused leaf by leaf
    so no whole transformed tree is ever held."""
    flat = [(weights.path_str(p), a)
            for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]]
    names = [n for p, a in flat for n, _ in _split(p, a, lead)]
    fn = fn or (lambda a: a)

    @jax.jit
    def norms(leaves, others):
        out = []
        for (p, _), a, *o in zip(flat, leaves, *others):
            for _, leaf in _split(p, fn(a, *o), lead):
                out.append(jnp.sqrt(jnp.sum(jnp.square(
                    leaf.astype(jnp.float32)))))
        return jnp.stack(out)

    values = np.asarray(norms([a for _, a in flat],
                              [jax.tree_util.tree_leaves(t) for t in more]))
    return dict(zip(names, values.tolist()))


def delta_norms(new, old, lead: int = 0) -> dict:
    """Leaf norms of ``new - old`` (``old`` broadcast over worker axes)."""
    return leaf_norms(new, lead, lambda a, b: a.astype(jnp.float32)
                      - b.astype(jnp.float32), old)


def lr_at(traffic: dict, step: int) -> float:
    """The phase's learning rate: linear decay from ``lr`` to 0 over
    ``lr_decay_steps``, no warm-up."""
    t = min(max(step / traffic["lr_decay_steps"], 0.0), 1.0)
    return traffic["lr"] * (1.0 - t)


def reference_steps(ref, cfg: dict, shapes, seed: int, batches, traffic,
                    mode: str = "f32") -> dict:
    """The reference's three steps: nesterov SGD with L2 weight decay
    (d = g + wd p; buf = m buf + d; p -= lr (d + m buf)), in float32."""
    m, wd = traffic["momentum"], traffic["weight_decay"]
    block_grad = jax.jit(jax.value_and_grad(
        lambda p, x, y: ref.loss(p, x, y, cfg, mode)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, s: jax.tree_util.tree_map(lambda x: x * s, a),
                    donate_argnums=(0,))

    def loss_grad(p, x, y):
        """The mean loss and its gradient over equal blocks of
        ``ref.GRAD_ROWS`` rows, where the reference sets it (the mean of the
        blocks' means), so a layer's backward holds one block's
        temporaries."""
        rows = getattr(ref, "GRAD_ROWS", None) or x.shape[0]
        n = x.shape[0] // rows
        loss, g = 0.0, None
        for i in range(n):
            lb, gb = block_grad(p, x[i * rows:(i + 1) * rows],
                                y[i * rows:(i + 1) * rows])
            loss += float(lb) / n
            g = gb if g is None else add(g, gb)
            del gb
        return loss, (g if n == 1 else scale(g, jnp.float32(1.0 / n)))

    def update(p, buf, g, lr):
        def leaf(p_, b_, g_):
            d = g_ + wd * p_
            b_ = m * b_ + d
            return p_ - lr * (d + m * b_), b_
        out = jax.tree_util.tree_map(leaf, p, buf, g)
        is_pair = lambda t: isinstance(t, tuple)          # noqa: E731
        return (jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_pair),
                jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_pair))

    update = jax.jit(update, donate_argnums=(0, 1))
    p = weights.make(shapes, seed, jnp.float32)
    buf = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norms, first_norms = [], None, None
    for step, b in enumerate(batches):
        loss, g = loss_grad(p, b["tokens"], b["labels"])
        losses.append(float(loss))
        if step == 0:
            grad_norms = leaf_norms(g)
            first_norms = leaf_norms(g, 0, lambda g_, p_: g_ + wd * p_, p)
        p, buf = update(p, buf, g, jnp.float32(lr_at(traffic, step)))
        del g
    del buf
    p0 = weights.make(shapes, seed, jnp.float32)
    return {"losses": losses, "grad": grad_norms, "first": first_norms,
            "delta": delta_norms(p, p0)}


def worst_gap(prog: dict, ref: dict, keep) -> tuple[float, str]:
    med = float(np.median([ref[n] for n in keep]))
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in keep}
    name = max(gaps, key=gaps.get)
    return gaps[name], name


def kept_leaves(grad_norms: dict) -> list:
    med = float(np.median(list(grad_norms.values())))
    return [n for n, v in grad_norms.items() if v >= EXCLUDE_BELOW * med]


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    """The compared numbers, each with its limit: those of ``loss_gap``,
    ``first_grad_gap`` and ``delta_gap`` that ``limits`` holds (a number
    that no control or fault separates from sound runs has no limit and is
    not compared). ``prog`` and ``ref`` hold ``losses`` (3), ``first`` and
    ``delta`` leaf norms."""
    keep = kept_leaves(ref["grad"])
    first_gap, first_at = worst_gap(prog["first"], ref["first"], keep)
    delta_gap, delta_at = worst_gap(prog["delta"], ref["delta"], keep)
    numbers = {
        "loss_gap": {"value": max(abs(a - b) / abs(b) for a, b in
                                  zip(prog["losses"], ref["losses"]))},
        "first_grad_gap": {"value": first_gap, "leaf": first_at},
        "delta_gap": {"value": delta_gap, "leaf": delta_at},
    }
    return {k: dict(v, limit=limits[k]) for k, v in numbers.items()
            if k in limits}
