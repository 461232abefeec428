"""Compiled continuous-batching engine: the serving analogue of the PR-3
scan-based training engine.

The per-step python ``ServingEngine`` (repro.serve.engine — kept as the
token-exact equivalence oracle and bench baseline) dispatches ONE jitted
decode per Python iteration and then blocks on ``int(next_tok[slot])`` for
every active slot — B×1 host syncs per generated token — and rebuilds the
whole cache pytree on the host at every admission. This engine moves the
hot loop under one compile:

  * **Device-resident scheduler state.** Slot state (next tokens, write
    positions, active flags, remaining-token budgets, per-slot EOS ids,
    sampling rng) lives on device as a ``DecodeState`` pytree alongside
    the cache. The host keeps only the request queue and a replay mirror.

  * **Fused multi-token decode.** One jit runs a ``lax.scan`` of
    ``decode_block`` (K) model steps: sampling (argmax or categorical),
    EOS detection, per-slot stopping, position/budget bookkeeping, and
    token buffering all happen on device. The host receives a single bulk
    ``(max_batch, K)`` token block per call — zero per-token round-trips —
    and replays the device's stop rule from that block alone.

  * **Jitted bulk admission.** A prefilled batch=1 cache is scattered into
    an engine slot with ``dynamic_update_slice`` over each leaf's batch
    dim — the dim named by ``repro.dist.cache_batch_dim``, the same rule
    ``cache_shardings`` uses to put that dim on the ``data`` mesh axis —
    replacing the old host-side leaf-by-leaf pytree rebuild.

  * **Bucketed prefill.** Prompts are right-padded to a small set of
    bucket lengths (``Model.prefill(length=...)`` makes the padding exact:
    same logits, window slots, and SSM states as the unpadded prompt), so
    warmup compiles a fixed program set instead of one program per
    distinct prompt length.

Scheduling differs from the oracle — admissions only happen between
K-token blocks, so a freed slot can idle for up to K-1 steps — but each
request's TOKENS are exact: a slot's output depends only on its own cache
rows, which admission re-prefills (asserted per-request against both the
python engine and single-request generation in tests/test_serve_compiled).

  * **Live weight publishing.** ``publish(params)`` hot-swaps a new weight
    generation (e.g. the phase-2 running average from
    ``repro.serve.publish.WeightPublisher``) without dropping in-flight
    requests: params are double-buffered on device, every slot is pinned
    to the generation it was admitted under, and while two generations are
    live the fused loop evaluates both and selects per-slot (bitwise — a
    swap never perturbs an admitted request's tokens). New admissions pick
    up the latest generation; the swap itself is pure host bookkeeping +
    one async host->device params transfer, so ``decode_transfers ==
    decode_calls`` holds across swaps (tests/test_publish.py).

  * **Paged KV cache.** With ``kv_layout="paged"`` (the default resolution
    of ``"auto"`` whenever the model has full-attention GQA layers), KV
    lives in a global device page pool plus per-slot block tables instead
    of one dense ``max_seq`` slab per slot: admission allocates only the
    pages the prompt needs, decode appends pages on demand (host-side
    allocation between fused calls — a tiny async h->d block-table upload,
    never a d->h sync), and a freed request returns its pages immediately.
    Memory then caps concurrency by RESIDENT TOKENS, not by
    slots x max_seq; ``kv_cache_dtype="int8"`` quantizes the pool
    (symmetric per-(token, head), models/attention.py) for ~4x more
    resident tokens per byte. Non-pageable layers (sliding-window, SSM,
    MLA, cross) keep their dense layout in the same cache tree; the page
    pool's layout/placement is owned by ``repro.dist.page_pool_dim``.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.dist.sharding import (batch_shardings, cache_batch_dim,
                                 cache_shardings, page_pool_dim,
                                 param_shardings, path_str)
from repro.models.model import Model
from repro.serve.engine import Request


class DecodeState(NamedTuple):
    """Device-resident scheduler state (a pytree; one leaf set per slot)."""

    cache: Any               # model KV/SSM cache, batch dim = slots
    tokens: jnp.ndarray      # (B,) int32 — next input token per slot
    positions: jnp.ndarray   # (B,) int32 — cache position `tokens` writes to
    active: jnp.ndarray      # (B,) bool  — slot currently generating
    remaining: jnp.ndarray   # (B,) int32 — decode steps left in the budget
    eos: jnp.ndarray         # (B,) int32 — per-slot EOS id, -1 = none
    rng: jnp.ndarray         # PRNG key for categorical sampling
    block_tables: jnp.ndarray  # (B, M) int32 page ids; (B, 0) when dense


def decode_state_shardings(mesh, state: DecodeState) -> DecodeState:
    """NamedSharding tree for a DecodeState: cache leaves by the
    ``cache_batch_dim`` / ``page_pool_dim`` rules, per-slot vectors (and
    block tables) batch-sharded, rng replicated — so a multi-host serving
    mesh places slots and pool pages on ``data``."""
    vec_sh = batch_shardings(
        mesh, {"tokens": state.tokens, "positions": state.positions,
               "active": state.active, "remaining": state.remaining,
               "eos": state.eos, "block_tables": state.block_tables})
    return DecodeState(
        cache=cache_shardings(mesh, state.cache),
        rng=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        **vec_sh)


def default_buckets(max_seq: int, lo: int = 16) -> Tuple[int, ...]:
    """Doubling prompt-length buckets: lo, 2lo, ... capped at max_seq."""
    buckets: List[int] = []
    b = lo
    while b < max_seq:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq)
    return tuple(buckets)


class CompiledServingEngine:
    """Drop-in sibling of ``ServingEngine`` with a compiled hot loop.

    Args beyond the oracle's: ``decode_block`` (K — model steps fused per
    host call), ``prefill_buckets`` (padded prompt lengths; None = doubling
    set from ``default_buckets``; always completed with ``max_seq`` so no
    prompt falls back to an uncounted exact-length compile), ``sample``
    ("greedy" | "categorical"), ``temperature`` and ``rng`` for sampling.

    Paged-cache args: ``kv_layout`` — "dense" (one max_seq cache row per
    slot), "paged" (global page pool + per-slot block tables for the
    model's pageable attention layers), or "auto" (paged iff the model has
    any pageable layer); ``page_size`` (tokens per page); ``n_pages``
    (pool size incl. the reserved null page 0; None = dense-equivalent
    capacity, so admission never waits on pages by default);
    ``kv_cache_dtype`` — overrides the model config's KV dtype (e.g.
    "int8") by rebuilding the Model on an updated config, so prefill,
    decode and the pool all quantize identically.

    Degradation args: ``admit_timeout_s`` — engine-wide bound on how long
    a request may wait for ADMISSION (a free slot + reservable pages);
    a request still waiting past it is shed with ``rejected=True`` /
    ``done=True`` and counted in ``stats["rejections"]``, instead of
    parking the FIFO head on an exhausted page pool forever. Per-request
    ``Request.deadline_s`` overrides it; None everywhere keeps the legacy
    wait-indefinitely behavior. ``clock`` is injectable (chaos tests use
    a fake clock; deadlines never sleep — they are checked at submit/step
    boundaries).
    """

    def __init__(self, model: Model, params, *, max_batch: int = 4,
                 max_seq: int = 256, decode_block: int = 8,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 sample: str = "greedy", temperature: float = 1.0,
                 rng=None, generation: int = 0,
                 kv_layout: str = "auto", page_size: int = 16,
                 n_pages: Optional[int] = None,
                 kv_cache_dtype: Optional[str] = None,
                 dist=None, admit_timeout_s: Optional[float] = None,
                 clock=time.monotonic):
        if sample not in ("greedy", "categorical"):
            raise ValueError(f"unknown sample mode {sample!r}")
        if kv_layout not in ("auto", "paged", "dense"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if kv_cache_dtype is not None \
                and kv_cache_dtype != model.cfg.kv_cache_dtype:
            # rebuild on the updated config so EVERY path (prefill scatter,
            # in-loop decode writes, pool leaves) quantizes the same way
            model = Model(dataclasses.replace(
                model.cfg, kv_cache_dtype=kv_cache_dtype))
        # dist (repro.dist.DistConfig): serving-mesh placement. Params land
        # by param_spec rules, decode state (cache + slot vectors) by
        # decode_state_shardings — slots and pool pages on `data`. None
        # (the default) keeps the single-device layout.
        if admit_timeout_s is not None and admit_timeout_s <= 0:
            raise ValueError(
                f"admit_timeout_s must be positive (None = no bound), "
                f"got {admit_timeout_s}")
        self.admit_timeout_s = admit_timeout_s
        self._clock = clock
        self.dist = dist
        self.mesh = dist.make_mesh() if dist is not None else None
        if self.mesh is not None:
            params = jax.device_put(
                params, param_shardings(self.mesh, params))
        self.model = model
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.decode_block = decode_block
        self.sample = sample
        self.temperature = temperature
        if kv_layout == "auto":
            kv_layout = "paged" if model.has_pageable else "dense"
        elif kv_layout == "paged" and not model.has_pageable:
            raise ValueError(
                "kv_layout='paged' but no layer of this model is pageable "
                "(full-attention GQA); use 'dense' or 'auto'")
        self.kv_layout = kv_layout
        self._paged = kv_layout == "paged"
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        # paged caches round the gathered length up to whole pages; the
        # rows past max_seq are never unmasked so tokens stay exact
        self._cache_len = (-(-max_seq // page_size) * page_size
                           if self._paged else max_seq)
        self._n_blocks = self._cache_len // page_size if self._paged else 0
        if n_pages is None:
            # dense-equivalent pool (+1 for the reserved null page)
            n_pages = max_batch * self._n_blocks + 1
        self.n_pages = n_pages if self._paged else 0
        if self._paged and self.n_pages < 2:
            raise ValueError("paged layout needs n_pages >= 2 "
                             "(page 0 is the reserved null page)")
        # host-owned allocator. Page 0 is never handed out: block-table
        # entries for unallocated/freed regions stay 0, so garbage writes
        # from frozen slots land on the null page and the position mask
        # keeps its rows out of every attention sum.
        self._free_pages: List[int] = list(range(1, self.n_pages))
        self.slot_pages: List[List[int]] = [[] for _ in range(max_batch)]
        self.slot_max_blocks: List[int] = [0] * max_batch
        self._host_bt = np.zeros((max_batch, self._n_blocks), np.int32)
        self._bt_dirty = False
        # double-buffered device-resident param sets: slot j of _buffers
        # holds weight generation _buf_gen[j]; _latest names the buffer new
        # admissions pin to. publish() fills the inactive buffer, so an
        # in-flight request keeps decoding on the exact weights it was
        # admitted under (see _decode_k_dual).
        self._buffers: List[Any] = [params, None]
        self._buf_gen: List[int] = [generation, generation - 1]
        self._latest: int = 0
        self._pending: Optional[Tuple[int, Any]] = None
        if prefill_buckets:
            bs = sorted({int(b) for b in prefill_buckets if b <= max_seq})
            if not bs or bs[-1] != max_seq:
                bs.append(max_seq)    # cap every bucket set at max_seq so
            self.buckets = tuple(bs)  # _bucket always finds a real bucket
        else:
            self.buckets = default_buckets(max_seq)
        self._compiled_buckets: set = set()
        self.state = self._empty_state(
            rng if rng is not None else jax.random.PRNGKey(0))
        if self.mesh is not None:
            self.state = jax.device_put(
                self.state, decode_state_shardings(self.mesh, self.state))
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_len: List[int] = [0] * max_batch   # prompt len per slot
        self.slot_buf: List[int] = [0] * max_batch   # pinned param buffer
        self.waiting: List[Request] = []
        # instrumentation consumed by benchmarks/bench_serve.py and
        # bench_publish.py: the zero-per-token-round-trip claim is
        # `decode_transfers == decode_calls` (one bulk block fetch per
        # fused call) — publishes must not add host syncs. ``queue_s`` is
        # the admitted requests' summed wait from submit to admission, on
        # the engine's clock; ``admissions`` counts them.
        self.stats: Dict[str, float] = {
            "decode_calls": 0, "decode_transfers": 0, "decode_steps": 0,
            "admissions": 0, "queue_s": 0.0, "prefill_compiles": 0,
            "publishes": 0, "publish_swaps": 0, "publish_superseded": 0,
            "dual_decode_calls": 0, "admit_page_waits": 0, "rejections": 0,
        }
        cache_len = self._cache_len
        self._prefill_fn = jax.jit(
            lambda p, t, L: self.model.prefill(p, t, cache_len=cache_len,
                                               length=L))
        self._admit_fn = jax.jit(self._admit_device, donate_argnums=(0,))
        self._decode_fn = jax.jit(self._decode_k, donate_argnums=(1,))
        self._decode_dual_fn = jax.jit(self._decode_k_dual,
                                       donate_argnums=(2,))

    @property
    def params(self):
        """The latest published parameter set (what new admissions use)."""
        return self._buffers[self._latest]

    @property
    def generation(self) -> int:
        """Weight generation new admissions are pinned to."""
        return self._buf_gen[self._latest]

    # ------------------------------------------------------------------
    # device programs
    # ------------------------------------------------------------------

    def _empty_state(self, rng) -> DecodeState:
        B = self.max_batch
        pool = (self.n_pages, self.page_size) if self._paged else None
        return DecodeState(
            cache=self.model.empty_cache(B, self._cache_len,
                                         page_pool=pool),
            tokens=jnp.zeros((B,), jnp.int32),
            positions=jnp.zeros((B,), jnp.int32),
            active=jnp.zeros((B,), bool),
            remaining=jnp.zeros((B,), jnp.int32),
            eos=jnp.full((B,), -1, jnp.int32),
            rng=rng,
            block_tables=jnp.zeros((B, self._n_blocks), jnp.int32))

    def _sample(self, logits, key):
        """(B, vocab) logits -> (B,) int32 next tokens."""
        if self.sample == "greedy":
            return jnp.argmax(logits, -1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / self.temperature,
            axis=-1).astype(jnp.int32)

    def _admit_device(self, state: DecodeState, prefill_cache, first_tok,
                      slot, length, budget, eos_id, active,
                      page_row) -> DecodeState:
        """Scatter a batch=1 prefill cache + fresh slot scalars into
        ``slot``. One compiled program for every admission (prefill caches
        are always padded to ``_cache_len``).

        Dense leaves land via ``dynamic_update_slice`` on the slot's batch
        row. Paged (``p``-layout) pool leaves take the prefill's DENSE
        ``a`` rows, fold them into whole pages, and scatter them to the
        slot's pages named by ``page_row`` — entries past the prompt are 0,
        so their (garbage) pages land on the reserved null page. The host
        block-table mirror is uploaded separately (see ``step``), never
        inside this donated program."""
        src = {path_str(kp): leaf for kp, leaf in
               jax.tree_util.tree_flatten_with_path(prefill_cache)[0]}

        def scatter(path, dst):
            # cache layout (batch dim / page dim) is owned by repro.dist —
            # the same rules cache_shardings uses to place leaves on `data`
            ps = path_str(path)
            pd = page_pool_dim(ps)
            if pd is not None:
                parts = ps.split("/")
                parts[-2] = "a"            # pool leaf <- dense prefill leaf
                leaf = src["/".join(parts)]
                rows = jnp.take(leaf, 0, axis=cache_batch_dim(ps))  # B=1
                M, P = page_row.shape[0], dst.shape[pd + 1]
                rows = rows.reshape(rows.shape[:pd] + (M, P)
                                    + rows.shape[pd + 1:]).astype(dst.dtype)
                if pd == 1:                # stacked-units pool
                    return dst.at[:, page_row].set(rows)
                return dst.at[page_row].set(rows)
            leaf = src[ps]
            bd = cache_batch_dim(ps)
            start = [jnp.int32(0)] * dst.ndim
            start[bd] = slot
            return jax.lax.dynamic_update_slice(
                dst, leaf.astype(dst.dtype), tuple(start))

        cache = jax.tree_util.tree_map_with_path(
            lambda path, dst: scatter(path, dst), state.cache)
        return DecodeState(
            cache=cache,
            tokens=state.tokens.at[slot].set(first_tok),
            positions=state.positions.at[slot].set(length),
            active=state.active.at[slot].set(active),
            remaining=state.remaining.at[slot].set(budget),
            eos=state.eos.at[slot].set(eos_id),
            rng=state.rng,
            block_tables=state.block_tables)

    def _advance(self, st: DecodeState, logits, cache):
        """Shared per-step bookkeeping after the model evaluation(s):
        sample, then mirror the oracle's step — positions advance, budgets
        tick, and a slot stops on budget, EOS, or max_seq-1 truncation,
        all checked AFTER the position increment, like
        ServingEngine._maybe_finish. Finished/free slots freeze so their
        (garbage) rows never index out of bounds. Identical ops in the
        single- and dual-generation programs, so tokens are bitwise
        independent of which program decoded them."""
        max_seq = self.max_seq
        rng, key = jax.random.split(st.rng)
        next_tok = self._sample(logits, key)
        act = st.active
        pos1 = jnp.where(act, st.positions + 1, st.positions)
        rem1 = jnp.where(act, st.remaining - 1, st.remaining)
        hit_eos = (st.eos >= 0) & (next_tok == st.eos)
        done = (rem1 <= 0) | hit_eos | (pos1 >= max_seq - 1)
        return DecodeState(
            cache=cache,
            tokens=jnp.where(act, next_tok, st.tokens),
            positions=pos1,
            active=act & ~done,
            remaining=rem1,
            eos=st.eos,
            rng=rng,
            block_tables=st.block_tables), next_tok

    def _decode_k(self, params, state: DecodeState):
        """K fused decode steps under one jit. Returns (state, (B, K) token
        block) — the block is the ONLY device->host traffic per call."""
        model = self.model

        def body(st: DecodeState, _):
            logits, cache = model.decode(params, st.cache,
                                         st.tokens[:, None], st.positions,
                                         block_tables=st.block_tables)
            return self._advance(st, logits, cache)

        state, toks = jax.lax.scan(body, state, None,
                                   length=self.decode_block)
        return state, toks.T                      # (K, B) -> (B, K)

    def _decode_k_dual(self, params_a, params_b, state: DecodeState, use_b,
                       use_b_pages):
        """K fused decode steps with TWO weight generations resident:
        every slot's logits and cache rows come from the param set its
        request was admitted under — ``jnp.where`` SELECTS between the two
        evaluations (never blends), so an in-flight request's tokens are
        bitwise identical to a single-generation engine pinned at its
        admission weights. Costs two model evaluations per step; the host
        dispatches this program only while generations are actually mixed
        (the old one drains as its requests finish). Still one bulk (B, K)
        transfer per call — publishing adds no host syncs.

        ``use_b_pages`` is the page-pool analogue of the per-slot ``use_b``
        selector: page i belongs to the slot that owns it, so selecting
        per PAGE on pool leaves is exactly selecting per slot (unowned
        pages hold garbage either way)."""
        model = self.model

        def body(st: DecodeState, _):
            logits_a, cache_a = model.decode(params_a, st.cache,
                                             st.tokens[:, None], st.positions,
                                             block_tables=st.block_tables)
            logits_b, cache_b = model.decode(params_b, st.cache,
                                             st.tokens[:, None], st.positions,
                                             block_tables=st.block_tables)
            logits = jnp.where(use_b[:, None], logits_b, logits_a)

            def pick(path, a, b):
                # broadcast the right selector along each cache leaf's
                # batch dim / page dim — the dims owned by repro.dist rules
                ps = path_str(path)
                pd = page_pool_dim(ps)
                sel, d = (use_b_pages, pd) if pd is not None \
                    else (use_b, cache_batch_dim(ps))
                shape = [1] * a.ndim
                shape[d] = a.shape[d]
                return jnp.where(sel.reshape(shape), b, a)

            cache = jax.tree_util.tree_map_with_path(pick, cache_a, cache_b)
            return self._advance(st, logits, cache)

        state, toks = jax.lax.scan(body, state, None,
                                   length=self.decode_block)
        return state, toks.T

    # ------------------------------------------------------------------
    # host scheduler
    # ------------------------------------------------------------------

    def _bucket(self, S: int) -> int:
        for b in self.buckets:
            if b >= S:
                return b
        # unreachable: construction always ends the bucket set at max_seq
        # and submit() rejects prompts longer than that
        raise AssertionError(f"no prefill bucket covers length {S}")

    def _run_prefill(self, bucket: int, padded, length):
        """Dispatch the bucketed prefill, counting the compile the first
        time each bucket's program is traced (warmup or post-warmup)."""
        if bucket not in self._compiled_buckets:
            self._compiled_buckets.add(bucket)
            self.stats["prefill_compiles"] += 1
        return self._prefill_fn(self.params, padded, jnp.int32(length))

    # ---- host page allocator (paged layout only) ----------------------

    def _full_blocks(self, S: int, max_new_tokens: int) -> int:
        """Worst-case pages a request can ever touch (prompt + budget,
        truncated at max_seq) — what admission must reserve."""
        last = min(S + max_new_tokens - 1, self.max_seq - 1)
        return last // self.page_size + 1

    def _reserved_pages(self) -> int:
        """Pages already promised to in-flight requests but not yet
        allocated. Admission keeps ``free >= reserved`` so mid-decode
        growth can never exhaust the pool."""
        return sum(self.slot_max_blocks[i] - len(self.slot_pages[i])
                   for i, r in enumerate(self.slot_req) if r is not None)

    def _alloc_slot_pages(self, slot: int, need: int) -> None:
        pages = self.slot_pages[slot]
        while len(pages) < need:
            if not self._free_pages:
                raise RuntimeError(
                    "page pool exhausted — admission reservation invariant "
                    "violated (this is a bug)")
            pid = self._free_pages.pop()
            self._host_bt[slot, len(pages)] = pid
            pages.append(pid)
            self._bt_dirty = True

    def _release_slot(self, slot: int) -> None:
        self.slot_req[slot] = None
        if self._paged:
            self._free_pages.extend(self.slot_pages[slot])
            self.slot_pages[slot] = []
            self.slot_max_blocks[slot] = 0
            if self._host_bt[slot].any():
                self._host_bt[slot] = 0
                self._bt_dirty = True

    def _ensure_pages(self) -> None:
        """Grow every active slot's block table to cover the rows the next
        fused block can write (host-side, between decode calls)."""
        K, P = self.decode_block, self.page_size
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            # device position the slot's NEXT write lands on
            p0 = self.slot_len[slot] + len(req.generated) - 1
            last = min(p0 + K - 1, self.max_seq - 1)
            # never past the admission-time reservation: a slot that stops
            # mid-block (budget/EOS) freezes at a row max_blocks covers,
            # so rows beyond it are never written while this slot owns it
            self._alloc_slot_pages(
                slot, min(last // P + 1, self.slot_max_blocks[slot]))

    def _push_block_tables(self) -> None:
        if self._bt_dirty:
            self.state = self.state._replace(
                block_tables=jnp.asarray(self._host_bt))  # async, tiny h->d
            self._bt_dirty = False

    def submit(self, request: Request) -> None:
        S = request.prompt.shape[0]
        if S > self.max_seq:
            raise ValueError(
                f"prompt of {S} tokens cannot fit the engine cache "
                f"(max_seq={self.max_seq})")
        if self._paged:
            full = self._full_blocks(S, request.max_new_tokens)
            if full > self.n_pages - 1:
                raise ValueError(
                    f"request needs {full} pages but the pool only has "
                    f"{self.n_pages - 1} allocatable (n_pages={self.n_pages},"
                    f" page_size={self.page_size})")
        request.submit_t = float(self._clock())
        self.waiting.append(request)
        self._admit()

    def _admit_deadline(self, req: Request) -> Optional[float]:
        d = req.deadline_s if req.deadline_s is not None \
            else self.admit_timeout_s
        if d is None:
            return None
        return (req.submit_t or 0.0) + d

    def _shed_expired(self) -> None:
        """Reject waiting requests whose admission deadline has passed —
        bounded head-of-line blocking: a request the pool cannot admit in
        time is shed explicitly (rejected=True) so the queue behind it
        keeps moving and callers never wait forever."""
        if not self.waiting:
            return
        now = float(self._clock())
        kept = []
        for req in self.waiting:
            deadline = self._admit_deadline(req)
            if deadline is not None and now > deadline:
                req.rejected = True
                req.done = True
                self.stats["rejections"] += 1
            else:
                kept.append(req)
        self.waiting = kept

    def _free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is None]

    def _admit(self) -> None:
        # re-derive free slots every iteration: a request that finishes AT
        # admission (budget 1 / instant EOS / truncation) leaves its slot
        # free for the next waiting request in this same pass; a deferred
        # publish is retried each iteration too, so a request admitted
        # after the blocking slot freed picks up the newest generation
        self._apply_pending()
        self._shed_expired()
        while self.waiting:
            self._apply_pending()
            free = self._free_slots()
            if not free:
                return
            full_blocks = 0
            if self._paged:
                # head-of-line page gate: reserve the request's worst-case
                # pages up front, or wait for in-flight requests to free
                # some (FIFO — no later, smaller request jumps the queue)
                head = self.waiting[0]
                full_blocks = self._full_blocks(head.prompt.shape[0],
                                                head.max_new_tokens)
                if (len(self._free_pages) - self._reserved_pages()
                        < full_blocks):
                    self.stats["admit_page_waits"] += 1
                    return
            slot = free[0]
            req = self.waiting.pop(0)
            with obs.span("serve.admit"):
                self._admit_one(req, slot, full_blocks)

    def _admit_one(self, req: Request, slot: int, full_blocks: int) -> None:
        """Prefill ``req`` into ``slot`` and seat it for decoding."""
        self.stats["admissions"] += 1
        self.stats["queue_s"] += float(self._clock()) - req.submit_t
        S = req.prompt.shape[0]
        bucket = self._bucket(S)
        padded = jnp.pad(req.prompt[None, :].astype(jnp.int32),
                         ((0, 0), (0, bucket - S)))
        with obs.span("serve.prefill"):
            logits, pc = self._run_prefill(bucket, padded, S)
            if self.sample == "greedy":
                tok = jnp.argmax(logits, -1).astype(jnp.int32)[0]
            else:
                self.state, key = self._split_host_key()
                tok = jax.random.categorical(
                    key, logits.astype(jnp.float32)
                    / self.temperature, axis=-1).astype(jnp.int32)[0]
            t0 = int(tok)                         # one scalar per ADMISSION
        req.generated = [t0]
        req.generation = self.generation          # pinned for its lifetime
        done0 = (req.max_new_tokens <= 1
                 or (req.eos_id is not None and t0 == req.eos_id)
                 or S >= self.max_seq - 1)
        page_row = np.zeros((self._n_blocks,), np.int32)
        if self._paged and not done0:
            # allocate only the PROMPT's pages now (rows 0..S — the
            # prompt plus the first decode write); growth happens
            # lazily in _ensure_pages as the request decodes
            self.slot_max_blocks[slot] = full_blocks
            self._alloc_slot_pages(
                slot, min(S // self.page_size + 1, full_blocks))
            page_row = self._host_bt[slot].copy()
        self.state = self._admit_fn(
            self.state, pc, tok, jnp.int32(slot), jnp.int32(S),
            jnp.int32(req.max_new_tokens - 1), jnp.int32(
                -1 if req.eos_id is None else req.eos_id),
            jnp.asarray(not done0), jnp.asarray(page_row))
        if done0:
            req.done = True
        else:
            self.slot_req[slot] = req
            self.slot_len[slot] = S
            self.slot_buf[slot] = self._latest

    def _split_host_key(self):
        rng, key = jax.random.split(self.state.rng)
        return self.state._replace(rng=rng), key

    # ------------------------------------------------------------------
    # live weight publishing
    # ------------------------------------------------------------------

    def publish(self, params,
                generation: Optional[int] = None) -> Optional[bool]:
        """Queue ``params`` as the next weight generation and swap it in as
        soon as the inactive buffer is free of pinned in-flight requests
        (often immediately). In-flight requests keep decoding on their
        admission-time weights; new admissions pick up the new generation.

        Only the newest queued publish survives — if another lands before
        a deferred one applied, the older is superseded (counted in
        ``stats['publish_superseded']``). Returns True when the swap
        happened inside this call, False when deferred (it will apply
        between decode calls once the old generation drains), and None
        when REJECTED as stale (``generation`` not newer than what the
        engine already serves or has queued) — so publishers can tell
        "delivered" (True/False) from "dropped" (None)."""
        base = self._buf_gen[self._latest]
        if self._pending is not None:
            base = max(base, self._pending[0])     # don't collide with a
        gen = base + 1 if generation is None else int(generation)  # queued gen
        if gen <= self._buf_gen[self._latest]:
            return None                           # stale republish
        if self._pending is not None:
            if gen <= self._pending[0]:
                return None
            self.stats["publish_superseded"] += 1
        self.stats["publishes"] += 1
        self._pending = (gen, params)
        return self._apply_pending()

    def _apply_pending(self) -> bool:
        """Swap the pending params into the inactive buffer unless a live
        request still pins it (double-buffering invariant: a buffer is
        only overwritten once no in-flight request can read it)."""
        if self._pending is None:
            return False
        target = 1 - self._latest
        if any(r is not None and self.slot_buf[i] == target
               for i, r in enumerate(self.slot_req)):
            return False                          # deferred: buffer busy
        with obs.span("serve.publish_swap"):
            gen, params = self._pending
            ref = self._buffers[self._latest]

            def place(new, old):
                new = jnp.asarray(new, getattr(old, "dtype", None))
                if new.shape != old.shape:
                    raise ValueError(
                        f"published params have leaf shape {new.shape} "
                        f"where the engine expects {old.shape} — "
                        f"generation published from a different model "
                        f"config?")
                return new

            # cast to the resident dtypes/shapes so the compiled decode
            # programs are reused as-is (a publish must never recompile)
            placed = jax.tree_util.tree_map(place, params, ref)
            if self.mesh is not None:
                # re-pin to the serving mesh: the cast above does not carry
                # the resident buffer's sharding over to the new generation
                placed = jax.device_put(placed,
                                        param_shardings(self.mesh, placed))
        self._buffers[target] = placed
        self._buf_gen[target] = gen
        self._latest = target
        self._pending = None
        self.stats["publish_swaps"] += 1
        return True

    @property
    def active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    def cache_bytes(self) -> int:
        """Device-resident bytes of the whole cache tree (page pool +
        dense leaves + scheduler vectors' cache) — what the paged/int8
        concurrency benchmark holds fixed across layouts."""
        return sum(int(l.nbytes)
                   for l in jax.tree_util.tree_leaves(self.state.cache))

    def step(self) -> None:
        """One fused K-token decode call for all slots, then a single bulk
        host transfer and a host-side replay of the device stop rule.

        The host knows which param buffer every active slot is pinned to
        (its replay mirror), so choosing the single- vs dual-generation
        program needs no device sync: the common case (all slots on one
        generation) runs exactly the pre-publishing program."""
        if self.active == 0:
            return
        if self._paged:
            self._ensure_pages()      # host alloc for the next K writes
            self._push_block_tables()
        bufs = {self.slot_buf[i] for i, r in enumerate(self.slot_req)
                if r is not None}
        with obs.span("serve.decode"):
            if len(bufs) == 1:
                self.state, block = self._decode_fn(
                    self._buffers[bufs.pop()], self.state)
            else:
                use_b = jnp.asarray(
                    [b == 1 for b in self.slot_buf])   # async, tiny, h->d
                use_b_pages = np.zeros((max(self.n_pages, 1),), bool)
                for i, r in enumerate(self.slot_req):
                    if r is not None and self.slot_buf[i] == 1:
                        use_b_pages[self.slot_pages[i]] = True
                self.state, block = self._decode_dual_fn(
                    self._buffers[0], self._buffers[1], self.state, use_b,
                    jnp.asarray(use_b_pages))
                self.stats["dual_decode_calls"] += 1
            self.stats["decode_calls"] += 1
            self.stats["decode_steps"] += self.decode_block
            block = np.asarray(block)             # ONE (B, K) transfer
            self.stats["decode_transfers"] += 1
        with obs.span("serve.replay"):
            self._replay(block)
        self._admit()

    def _replay(self, block) -> None:
        """Append each slot's tokens of the fused call's ``(B, K)`` block
        and apply the device's stop rule on the host."""
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            for k in range(self.decode_block):
                t = int(block[slot, k])
                req.generated.append(t)
                n = len(req.generated)
                pos_after = self.slot_len[slot] + n - 1
                if (n >= req.max_new_tokens
                        or (req.eos_id is not None and t == req.eos_id)
                        or pos_after >= self.max_seq - 1):
                    req.done = True
                    self._release_slot(slot)      # pages return to the pool
                    break

    def run(self, requests: List[Request], max_steps: int = 10_000
            ) -> Dict[int, List[int]]:
        """Serve requests to completion; returns rid -> tokens."""
        for r in requests:
            self.submit(r)
        steps = 0
        while (self.active or self.waiting) and steps < max_steps:
            self.step()
            steps += 1
        return {r.rid: r.generated for r in requests}

    # ------------------------------------------------------------------

    def warmup(self, dual: bool = False) -> None:
        """Compile the fixed program set (one prefill per bucket, the
        admission scatter, the fused decode block) before serving.
        ``dual=True`` additionally compiles the two-generation decode
        program, so the first mid-flight publish pays no compile — pass it
        when the engine will receive live weight swaps."""
        dummy = jnp.zeros((1, self.buckets[0]), jnp.int32)
        _, pc = self._run_prefill(self.buckets[0], dummy, 1)
        for b in self.buckets[1:]:
            self._run_prefill(b, jnp.zeros((1, b), jnp.int32), 1)
        st = self._empty_state(jax.random.PRNGKey(0))
        st = self._admit_fn(st, pc, jnp.int32(0), jnp.int32(0),
                            jnp.int32(1), jnp.int32(0), jnp.int32(-1),
                            jnp.asarray(False),
                            jnp.zeros((self._n_blocks,), jnp.int32))
        st, _ = self._decode_fn(self.params, st)
        if dual:
            other = self._buffers[1 - self._latest]
            st, _ = self._decode_dual_fn(
                self.params, other if other is not None else self.params,
                st, jnp.zeros((self.max_batch,), bool),
                jnp.zeros((max(self.n_pages, 1),), bool))
        jax.block_until_ready(st.tokens)
