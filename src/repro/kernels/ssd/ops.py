"""Public SSD scan op (Mamba-2 state-space duality).

``impl="reference"``: chunked pure-jnp SSD — intra-chunk quadratic block plus
log-depth associative scan over chunk states. Same algorithm and memory
behaviour as the kernel path; used for lowering/dry-run and CPU training.

``impl="pallas"``: intra-chunk block from the compiled kernel for the live
backend — Mosaic (kernel.py) on TPU, Triton (kernel_gpu.py) on GPU — with
the inter-chunk correction in JAX; ``impl="mosaic"``/``impl="triton"``
force a lowering (interpreter off its native backend). Backward runs the
matching intra-chunk backward kernel (custom_vjp).

``impl="naive"``: the sequential-recurrence oracle (tests only).

``impl="auto"`` (the config default): backend-resolved — compiled Mosaic on
TPU, compiled Triton on GPU, the chunked reference on CPU
(repro.kernels.dispatch); the Triton path carries the tuning-cache design
point (num_warps/num_stages) unless the caller pins one via ``design``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import dispatch
from repro.kernels.ssd import ref as _ref
from repro.kernels.ssd.kernel import ssd_chunk_pallas, ssd_chunk_pallas_bwd
from repro.kernels.ssd.kernel_gpu import ssd_chunk_triton, ssd_chunk_triton_bwd


def _intra_chunk_jnp(x, dt, A, Bm, Cm, chunk):
    """jnp twin of the Pallas intra-chunk kernel.
    Returns (y_intra, states (B,nc,H,P,N), cum (B,S,H)) in f32."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    nc = S // chunk
    xf = x.astype(jnp.float32).reshape(Bsz, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, chunk, H)
    bf = Bm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)
    cf = Cm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N)

    dA = dtf * A.astype(jnp.float32)                     # (B,nc,L,H)
    cum = jnp.cumsum(dA, axis=2)
    # seg[i,j] = exp(cum_i - cum_j), lower triangular
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,L,L,H)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE exp: upper-triangle diff is positive (cum decreasing), and
    # where(mask, exp(diff), 0) would produce 0*inf = NaN in the backward.
    seg = jnp.exp(jnp.where(tri[None, None, :, :, None], diff, -jnp.inf))

    # scores[i,j] = C_i · B_j  (per group), expanded to heads
    scores = jnp.einsum("bclgn,bcmgn->bclmg", cf, bf)      # (B,nc,L,L,G)
    scores = jnp.repeat(scores, rep, axis=-1)              # (B,nc,L,L,H)
    dx = dtf[..., None] * xf                               # (B,nc,L,H,P)
    y_intra = jnp.einsum("bclmh,bcmhp->bclhp", scores * seg, dx)

    # chunk-local final states
    w = jnp.exp(cum[:, :, -1:, :] - cum) * dtf             # (B,nc,L,H)
    bw = jnp.repeat(bf, rep, axis=3) * w[..., None]        # (B,nc,L,H,N)
    states = jnp.einsum("bclhp,bclhn->bchpn", xf, bw)      # (B,nc,H,P,N)

    cum_full = cum.reshape(Bsz, S, H)
    return y_intra.reshape(Bsz, S, H, P), states, cum_full


def _inter_chunk(y_intra, states, cum, x, dt, A, Cm, D, chunk, init_state):
    """Combine chunk-local states into the full scan and add corrections."""
    Bsz, S, H, P = y_intra.shape
    G, N = Cm.shape[2], Cm.shape[3]
    rep = H // G
    nc = S // chunk
    cumr = cum.reshape(Bsz, nc, chunk, H)
    chunk_decay = jnp.exp(cumr[:, :, -1, :])               # (B,nc,H)

    # recurrence s_c = a_c * s_{c-1} + b_c  via associative scan over chunks
    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, b2 + a2[..., None, None] * b1

    a = chunk_decay
    b = states
    if init_state is not None:
        b = b.at[:, 0].add(a[:, 0][..., None, None] *
                           init_state.astype(jnp.float32))
    a_scan, s_after = jax.lax.associative_scan((combine), (a, b), axis=1)
    # state entering chunk c
    s_in = jnp.concatenate(
        [jnp.zeros_like(s_after[:, :1]) if init_state is None
         else init_state.astype(jnp.float32)[:, None],
         s_after[:, :-1]], axis=1)                         # (B,nc,H,P,N)

    cf = jnp.repeat(Cm.astype(jnp.float32).reshape(Bsz, nc, chunk, G, N),
                    rep, axis=3)                           # (B,nc,L,H,N)
    y_inter = jnp.einsum("bclhn,bchpn->bclhp", cf, s_in)
    y_inter = y_inter * jnp.exp(cumr)[..., None]
    y = y_intra + y_inter.reshape(Bsz, S, H, P)
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y, s_after[:, -1]


def _chunked_reference(x, dt, A, Bm, Cm, D, chunk, init_state):
    S = x.shape[1]
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y_intra, states, cum = _intra_chunk_jnp(x, dt, A, Bm, Cm, chunk)
    y, final = _inter_chunk(y_intra, states, cum, x, dt, A, Cm, D, chunk,
                            init_state)
    if pad:
        y = y[:, :S]
        # final state including padded zeros: dt padding = 0 -> decay 1,
        # contribution 0, so the final state is exact.
    return y, final


def _intra_fwd(variant, xp, dtp, A, Bmp, Cmp, c, design, interpret):
    if variant == "triton":
        return ssd_chunk_triton(xp, dtp, A, Bmp, Cmp, chunk=c,
                                design=design, interpret=interpret)
    return ssd_chunk_pallas(xp, dtp, A, Bmp, Cmp, chunk=c,
                            interpret=interpret)


def _intra_bwd(variant, xp, dtp, A, Bmp, Cmp, d_yi, d_st, d_cum, c, design,
               interpret):
    if variant == "triton":
        return ssd_chunk_triton_bwd(xp, dtp, A, Bmp, Cmp, d_yi, d_st,
                                    d_cum, chunk=c, design=design,
                                    interpret=interpret)
    return ssd_chunk_pallas_bwd(xp, dtp, A, Bmp, Cmp, d_yi, d_st, d_cum,
                                chunk=c, interpret=interpret)


# chunk, variant, design and interpret (args 7-10, all static/hashable) are
# nondiff argnums — bwd takes them first.
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _pallas_ssd(x, dt, A, Bm, Cm, D, init_state, chunk, variant, design,
                interpret):
    S = x.shape[1]
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y_intra, states, cum = _intra_fwd(variant, x, dt, A, Bm, Cm, c, design,
                                      interpret)
    y, final = _inter_chunk(y_intra, states, cum, x, dt, A, Cm, D, c,
                            init_state)
    if pad:
        y = y[:, :S]
    return y, final


def _pallas_fwd(x, dt, A, Bm, Cm, D, init_state, chunk, variant, design,
                interpret):
    S = x.shape[1]
    c = min(chunk, S)
    pad = (-S) % c
    xp, dtp, Bmp, Cmp = x, dt, Bm, Cm
    if pad:
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmp = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cmp = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y_intra, states, cum = _intra_fwd(variant, xp, dtp, A, Bmp, Cmp, c,
                                      design, interpret)
    y, final = _inter_chunk(y_intra, states, cum, xp, dtp, A, Cmp, D, c,
                            init_state)
    if pad:
        y = y[:, :S]
    return (y, final), (xp, dtp, A, Bmp, Cmp, D, init_state, y_intra,
                        states, cum, pad, c)


def _pallas_bwd(chunk, variant, design, interpret, res, g):
    """True kernel backward: jnp autodiff through the (cheap) inter-chunk
    combine, then the Pallas intra-chunk backward kernel for the O(L²)
    part — no full forward recompute."""
    xp, dtp, A, Bmp, Cmp, D, init_state, y_intra, states, cum, pad, c = res
    dy, dfinal = g
    S = xp.shape[1] - pad
    if pad:
        dy = jnp.pad(dy, ((0, 0), (0, pad), (0, 0), (0, 0)))

    def inter(y_intra, states, cum, x_, Cm_, D_, init_):
        return _inter_chunk(y_intra, states, cum, x_, dtp, A, Cm_, D_, c,
                            init_)
    if init_state is None:
        _, vjp = jax.vjp(lambda yi, st, cu, x_, Cm_, D_: inter(
            yi, st, cu, x_, Cm_, D_, None), y_intra, states, cum, xp, Cmp, D)
        d_yi, d_st, d_cum, dx1, dCm1, dD = vjp((dy, dfinal))
        d_init = None
    else:
        _, vjp = jax.vjp(inter, y_intra, states, cum, xp, Cmp, D, init_state)
        d_yi, d_st, d_cum, dx1, dCm1, dD, d_init = vjp((dy, dfinal))

    dx2, ddt, dA, dBm, dCm2 = _intra_bwd(
        variant, xp, dtp, A, Bmp, Cmp, d_yi, d_st, d_cum, c, design,
        interpret)
    dx = dx1.astype(jnp.float32) + dx2
    dCm = dCm1.astype(jnp.float32) + dCm2
    if pad:
        dx, ddt = dx[:, :S], ddt[:, :S]
        dBm, dCm = dBm[:, :S], dCm[:, :S]
    return (dx.astype(xp.dtype), ddt.astype(dtp.dtype), dA.astype(A.dtype),
            dBm.astype(Bmp.dtype), dCm.astype(Cmp.dtype),
            None if D is None else dD, d_init)


_pallas_ssd.defvjp(_pallas_fwd, _pallas_bwd)


def ssd_scan(x, dt, A, Bm, Cm, D=None, *, init_state=None, chunk: int = 128,
             impl: str = "auto", design=None):
    """Mamba-2 SSD scan. x: (B,S,H,P); dt: (B,S,H) post-softplus; A: (H,)
    negative; Bm, Cm: (B,S,G,N); D: (H,) or None.
    Returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32).
    ``design`` pins a tuning design point (DesignPoint or 4-tuple);
    default None consults the tuning cache for the resolved backend."""
    d = dispatch.resolve(impl, kernel="ssd",
                         shape=(x.shape[1], x.shape[3]), design=design)
    if d.impl == "naive":
        return _ref.ssd_ref(x, dt, A, Bm, Cm, D, init_state)
    if d.impl == "pallas":
        if d.variant == "mosaic":
            # heads over groups: how many heads share one C·Bᵀ
            obs.count("ssd.heads", x.shape[0] * x.shape[2])
            obs.count("ssd.groups", x.shape[0] * Bm.shape[2])
        return _pallas_ssd(x, dt, A, Bm, Cm, D, init_state, chunk,
                           d.variant, d.design, d.interpret)
    return _chunked_reference(x, dt, A, Bm, Cm, D, chunk, init_state)


ssd_decode = _ref.ssd_decode_ref
