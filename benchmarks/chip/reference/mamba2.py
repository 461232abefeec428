"""Plain reference of Mamba-2 (arXiv:2405.21060; ``state-spaces/mamba2``):
pre-norm residual layers of RMSNorm -> Mamba-2 mixer, a final RMSNorm and
an LM head tied to the embedding.

The mixer, as ``mamba_ssm``'s ``Mamba2`` computes it with ngroups groups:
in_proj -> [z, x, B, C, dt]; a depthwise causal conv (width d_conv, with
bias) and SiLU over [x, B, C]; dt = softplus(dt + dt_bias); A = -exp(A_log);
the selective state space as a recurrence, one time step after the other:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

per head (h: headdim x d_state), then y = RMSNorm(y * silu(z)) over the
whole inner width (one group) and out_proj. Not the chunked algorithm.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. Blockwise only where the
published widths need it to fit one chip: the recurrence is recomputed in
the backward from states kept every ``T_BLOCK`` steps, layers and loss rows
likewise (``jax.checkpoint``), and the training gradient is taken one
sequence at a time and averaged (``chipbench.traincheck``). Weights are
read by path from the tree the benchmark made (the program's layout).
Departures from the published model: none in the mathematics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.refmath import mm, rms_norm

T_BLOCK = 64         # time steps per recomputed block of the recurrence
ROW_BLOCK = 1024     # rows per block of the LM head and loss
GRAD_ROWS = 1        # sequences per block of the training gradient (a
                     # layer's backward holds about 1.7 GiB per sequence of
                     # 2048 at the published widths)


def dims(cfg):
    s = cfg["ssm_cfg"]
    d_in = s["expand"] * cfg["d_model"]
    G, N, P = s["ngroups"], s["d_state"], s["headdim"]
    return d_in, G, N, P, d_in // P, s["d_conv"]


def causal_conv(x, w, b):
    """Depthwise causal conv: out[t] = b + sum_k w[k] x[t - K + 1 + k]."""
    K = w.shape[0]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[k] * xp[:, k:k + S] for k in range(K))


def scan_ssm(x, dt, A, Bm, Cm, D):
    """x: (B, S, H, P); dt: (B, S, H); Bm, Cm: (B, S, G, N). Returns y."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)                         # (B, S, H, N)
    Ch = jnp.repeat(Cm, rep, axis=2)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * A)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    @jax.checkpoint
    def block(h, inp):
        return jax.lax.scan(step, h, inp)

    tb = min(T_BLOCK, S)
    nb = S // tb

    def blocks(a):            # (B, S, ...) -> (nb, tb, B, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((nb, tb) + a.shape[1:])

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, y = jax.lax.scan(block, h0, tuple(blocks(a) for a in (x, dt, Bh, Ch)))
    y = jnp.moveaxis(y.reshape((S,) + y.shape[2:]), 0, 1)    # (B, S, H, P)
    return y + D[:, None] * x


def mixer(u, p, cfg, mode):
    d_in, G, N, P, H, _ = dims(cfg)
    Bsz, S, _ = u.shape
    proj = mm(u, p["in_proj"], mode)
    z, xbc, dt = (proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * G * N],
                  proj[..., 2 * d_in + 2 * G * N:])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :d_in].reshape(Bsz, S, H, P)
    Bm = xbc[..., d_in:d_in + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., d_in + G * N:].reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = scan_ssm(x, dt, A, Bm, Cm, p["D"]).reshape(Bsz, S, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], cfg["norm_epsilon"])
    return mm(y, p["out_proj"], mode)


def layer(h, p, cfg, mode):
    return h + mixer(rms_norm(h, p["ln1"]["scale"], cfg["norm_epsilon"]),
                     p["mamba"], cfg, mode)


def hidden(params, tokens, cfg, mode):
    h = params["embed"]["table"][tokens]
    blocks = jax.tree_util.tree_map(lambda a: a[:, 0], params["blocks"])

    def body(h, p):
        return jax.checkpoint(functools.partial(layer, cfg=cfg,
                                                mode=mode))(h, p), None

    h, _ = jax.lax.scan(body, h, blocks)
    return rms_norm(h, params["final_norm"]["scale"], cfg["norm_epsilon"])


def logits(params, h, mode):
    return mm(h, params["embed"]["table"].T, mode)


def loss(params, tokens, labels, cfg, mode="f32"):
    """Mean next-token cross-entropy over every position."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        h = hidden(params, tokens, cfg, mode)
        d = h.shape[-1]
        hr, yr = h.reshape(-1, d), labels.reshape(-1)
        rb = min(ROW_BLOCK, hr.shape[0])
        n = hr.shape[0] // rb

        @jax.checkpoint
        def block(i):
            hb = jax.lax.dynamic_slice_in_dim(hr, i * rb, rb)
            yb = jax.lax.dynamic_slice_in_dim(yr, i * rb, rb)
            lg = logits(params, hb, mode)
            lse = jax.nn.logsumexp(lg, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], 1)[:, 0])

        return jnp.sum(jax.lax.map(block, jnp.arange(n))) / hr.shape[0]
