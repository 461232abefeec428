"""Operations and bytes that the algorithms need, computed from the
published shapes alone.

Counts are of what the mathematics requires for a call, never of what an
implementation happens to do: causal attention counts the causal half of the
score matrix, GQA reads each K/V head once, and recomputation (activation
checkpointing, the flash backward re-running its forward) is not counted.
A kernel that skips masked blocks, or stops recomputing, therefore moves
towards 100% of its roofline and can never pass it.

One multiply-add is 2 operations. What a model needs per token lives in
``models/<model_type>.py``, which calls these kernel counts.
"""
from __future__ import annotations


def causal_pairs(S: int) -> float:
    """Query-key pairs under a causal mask: sum over positions of i+1."""
    return S * (S + 1) / 2


# ---------------------------------------------------------------- kernels


def flash_attention_train(B: int, S: int, H: int, KVH: int, Dh: int,
                          itemsize: int = 2) -> tuple[float, float]:
    """One causal self-attention call, forward and backward, as the
    training step needs it. Operations: forward QK^T and PV; backward
    dP = dO V^T, dV = P^T dO, dK = dS^T Q and dQ = dS K: six matmuls over
    the causal half (flash attention's re-run of QK^T in the backward is
    recomputation and not counted). Bytes: forward reads q, k, v and
    writes o and the f32 log-sum-exp; backward reads q, k, v, o, dO and the
    log-sum-exp and writes dq, dk, dv. K/V are read once per KV head."""
    pairs = B * H * causal_pairs(S)
    ops = 6 * 2 * Dh * pairs
    q = o = B * S * H * Dh * itemsize
    kv = B * S * KVH * Dh * itemsize
    lse = B * H * S * 4
    fwd_bytes = q + 2 * kv + o + lse
    bwd_bytes = (q + 2 * kv + o + q + lse) + (q + 2 * kv)
    return ops, fwd_bytes + bwd_bytes


def ssd_train(B: int, S: int, H: int, P: int, G: int, N: int, chunk: int,
              x_itemsize: int, bc_itemsize: int) -> tuple[float, float]:
    """The intra-chunk SSD block (the Pallas kernel's job), forward and
    backward. Forward operations per chunk: scores C.B^T over the causal
    half (per group), the masked scores times dt*x (per head) and the chunk
    state B^T (dt x) (per head); the backward is twice the forward (two
    gradient matmuls per matmul). Bytes: forward reads x, dt (f32), B, C
    and A, and writes y (f32), the chunk states (f32) and the cumulative
    decay (f32); backward reads those inputs and the three cotangents and
    writes dx, ddt, dB, dC."""
    nc = S // chunk
    pairs = causal_pairs(chunk)
    fwd = B * nc * (2 * G * N * pairs + 2 * H * P * pairs
                    + 2 * H * chunk * P * N)
    x = B * S * H * P * x_itemsize
    dt = B * S * H * 4
    bc = 2 * B * S * G * N * bc_itemsize
    y = B * S * H * P * 4
    states = B * nc * H * P * N * 4
    cum = B * S * H * 4
    fwd_bytes = x + dt + bc + y + states + cum
    bwd_bytes = (x + dt + bc) + (y + states + cum) + (x + dt + bc)
    return 3 * fwd, fwd_bytes + bwd_bytes


def roofline(ops: float, nbytes: float, seconds: float,
             peak_flops: float, peak_bw: float) -> tuple[float, str]:
    """Share (%) of the roofline: the least time the chip could take, the
    larger of ops over peak operations and bytes over peak bandwidth,
    divided by the measured time; and which of the two bounds it."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound


def kernel_roofline(ctx: dict, kernel: str) -> float | None:
    """A per-layer reader's roofline share (%) of ``kernel`` in a traced
    run: the operations and bytes the job counted for the window's calls,
    against the kernel's device time in the trace and the chip's bf16 and
    HBM peaks; None where the run has no such kernel or no trace."""
    work = ctx["facts"].get("kernels", {}).get(kernel)
    seconds = ctx["trace"]["kernel_s"].get(kernel) if ctx["trace"] else None
    if not work or not seconds:
        return None
    share, _ = roofline(work["ops"], work["bytes"], seconds,
                        ctx["peaks"]["bf16_flops"],
                        ctx["peaks"]["hbm_bytes_per_s"])
    return share
