"""Compiled serving engine: per-request token-exactness vs the per-step
oracle (ServingEngine) and vs single-request generation, under staggered
arrivals, mid-stream EOS, slot reuse, and max_seq truncation — plus the
one-bulk-transfer-per-fused-call instrumentation contract."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.launch.serve import generate
from repro.models.model import Model
from repro.serve.compiled import (CompiledServingEngine, decode_state_shardings,
                                  default_buckets)
from repro.serve.engine import Request, ServingEngine

_SETUP_CACHE = {}


def _setup(arch):
    if arch not in _SETUP_CACHE:
        cfg = registry.get_smoke_config(arch)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _SETUP_CACHE[arch] = (cfg, model, params)
    return _SETUP_CACHE[arch]


def _prompts(cfg, lengths, seed=1):
    key = jax.random.PRNGKey(seed)
    return [jax.random.randint(jax.random.fold_in(key, i), (L,), 0,
                               cfg.vocab_size, dtype=jnp.int32)
            for i, L in enumerate(lengths)]


def _reference_tokens(model, params, prompt, n_new):
    out, _ = generate(model, params, prompt[None, :], n_new)
    return [int(t) for t in out[0]]


# the acceptance pair: one attention-KV arch, one SSM-cache arch
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-2.7b"])
def test_compiled_matches_oracle_and_generate(arch):
    """5 requests of different prompt lengths through 2 slots: the
    compiled engine must produce EXACTLY the oracle engine's tokens AND
    each request's isolated-generation tokens — bucketed (padded) prefill,
    the jitted admission scatter, and the fused decode loop all preserve
    per-request tokens."""
    cfg, model, params = _setup(arch)
    lengths = [9, 17, 5, 12, 8]
    n_new = 6
    prompts = _prompts(cfg, lengths)
    reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=n_new)  # noqa: E731
                    for i, p in enumerate(prompts)]

    oracle = ServingEngine(model, params, max_batch=2, max_seq=64)
    want = oracle.run(reqs())
    engine = CompiledServingEngine(model, params, max_batch=2, max_seq=64,
                                   decode_block=4)
    got = engine.run(reqs())

    for i, p in enumerate(prompts):
        assert got[i] == want[i], (arch, i, "vs oracle")
        assert got[i] == _reference_tokens(model, params, p, n_new), \
            (arch, i, "vs generate")
    # fused-loop contract: one bulk (B, K) transfer per decode call
    assert engine.stats["decode_transfers"] == engine.stats["decode_calls"]
    assert engine.stats["decode_calls"] > 0


def test_sliding_window_arch_with_padded_buckets():
    """gemma3 (sliding-window + full attention layers): bucket padding
    must keep circular window slots arranged by REAL positions."""
    cfg, model, params = _setup("gemma3-1b")
    prompts = _prompts(cfg, [7, 13])
    n_new = 5
    engine = CompiledServingEngine(model, params, max_batch=2, max_seq=64,
                                   decode_block=2, prefill_buckets=(16, 64))
    got = engine.run([Request(rid=i, prompt=p, max_new_tokens=n_new)
                      for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        assert got[i] == _reference_tokens(model, params, p, n_new), i


def test_staggered_arrivals():
    """Requests submitted mid-stream (after decode blocks already ran)
    still come out token-exact; late arrivals wait for a free slot."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, [9, 6, 11, 7], seed=3)
    n_new = 8
    engine = CompiledServingEngine(model, params, max_batch=2, max_seq=64,
                                   decode_block=3)
    first = [Request(rid=i, prompt=prompts[i], max_new_tokens=n_new)
             for i in range(2)]
    late = [Request(rid=i, prompt=prompts[i], max_new_tokens=n_new)
            for i in range(2, 4)]
    for r in first:
        engine.submit(r)
    engine.step()                      # decode a block before anyone new
    engine.submit(late[0])
    engine.step()
    engine.submit(late[1])
    steps = 0
    while (engine.active or engine.waiting) and steps < 100:
        engine.step()
        steps += 1
    for i, p in enumerate(prompts):
        want = _reference_tokens(model, params, p, n_new)
        assert (first + late)[i].generated == want, i


def test_mid_stream_eos():
    """A request whose EOS appears mid-block stops exactly where the
    oracle stops (device-side EOS detection + host replay agree)."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompt = _prompts(cfg, [8], seed=5)[0]
    ref = _reference_tokens(model, params, prompt, 6)
    # pick an eos whose FIRST occurrence is past the first token, so it
    # fires inside a decode block rather than at admission
    stop = next(j for j in range(1, len(ref)) if ref[j] not in ref[:j])
    eos = ref[stop]

    oracle = ServingEngine(model, params, max_batch=2, max_seq=32)
    r_o = Request(rid=0, prompt=prompt, max_new_tokens=10, eos_id=eos)
    oracle.run([r_o])
    engine = CompiledServingEngine(model, params, max_batch=2, max_seq=32,
                                   decode_block=4)
    r_c = Request(rid=0, prompt=prompt, max_new_tokens=10, eos_id=eos)
    engine.run([r_c])
    assert r_c.generated == r_o.generated
    assert r_c.generated[-1] == eos and len(r_c.generated) == stop + 1
    assert engine.stats["decode_calls"] > 0
    assert r_c.done


def test_eos_as_first_token_finishes_at_admission():
    cfg, model, params = _setup("internlm2-1.8b")
    prompt = _prompts(cfg, [8], seed=6)[0]
    ref = _reference_tokens(model, params, prompt, 2)
    engine = CompiledServingEngine(model, params, max_batch=1, max_seq=32,
                                   decode_block=4)
    req = Request(rid=0, prompt=prompt, max_new_tokens=10, eos_id=ref[0])
    engine.run([req])
    assert req.done and req.generated == [ref[0]]
    assert engine.stats["decode_calls"] == 0


def test_slot_reuse_after_free():
    """3 requests through ONE slot: each admission re-prefills the slot's
    cache rows, so request n+1 is token-exact despite inheriting a dirty
    slot (and dirty garbage positions) from request n."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, [6, 10, 7], seed=7)
    engine = CompiledServingEngine(model, params, max_batch=1, max_seq=32,
                                   decode_block=4)
    results = engine.run([Request(rid=i, prompt=p, max_new_tokens=4)
                          for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        assert results[i] == _reference_tokens(model, params, p, 4), i
    assert engine.active == 0 and not engine.waiting


def test_admission_chain_when_request_finishes_at_admission():
    """A request that finishes AT admission (budget 1) must not strand the
    requests queued behind it: its slot frees inside the same admission
    pass. (Regression: the free-slot list was computed once per pass, so
    the follow-up request waited forever on one-slot engines — on both
    engines.)"""
    cfg, model, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, [6, 8, 7], seed=15)
    for cls in (CompiledServingEngine, ServingEngine):
        kw = {"decode_block": 4} if cls is CompiledServingEngine else {}
        engine = cls(model, params, max_batch=1, max_seq=32, **kw)
        reqs = [Request(rid=0, prompt=prompts[0], max_new_tokens=5),
                Request(rid=1, prompt=prompts[1], max_new_tokens=1),
                Request(rid=2, prompt=prompts[2], max_new_tokens=5)]
        results = engine.run(reqs, max_steps=200)
        assert all(r.done for r in reqs), cls.__name__
        assert results[1] == _reference_tokens(model, params, prompts[1],
                                               1), cls.__name__
        assert results[2] == _reference_tokens(model, params, prompts[2],
                                               5), cls.__name__


def test_max_seq_truncation():
    """A request that would run past max_seq-1 truncates at exactly the
    oracle's stopping point (position check after the increment)."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompt = _prompts(cfg, [10], seed=9)[0]

    oracle = ServingEngine(model, params, max_batch=1, max_seq=16)
    r_o = Request(rid=0, prompt=prompt, max_new_tokens=50)
    oracle.run([r_o])
    engine = CompiledServingEngine(model, params, max_batch=1, max_seq=16,
                                   decode_block=4)
    r_c = Request(rid=0, prompt=prompt, max_new_tokens=50)
    engine.run([r_c])
    assert r_c.generated == r_o.generated
    assert len(r_c.generated) < 50     # truncated, not budget-stopped
    assert r_c.done


def test_decode_block_size_invariance():
    """K is a throughput knob, not a semantics knob: K=1 and K=5 produce
    identical tokens for the same workload."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, [9, 12], seed=11)

    def run(block):
        engine = CompiledServingEngine(model, params, max_batch=2,
                                       max_seq=48, decode_block=block)
        return engine.run([Request(rid=i, prompt=p, max_new_tokens=7)
                           for i, p in enumerate(prompts)])

    assert run(1) == run(5)


def test_categorical_sampling_runs():
    """Sampled decode (device-side categorical) produces the right token
    counts and stays reproducible for a fixed engine rng."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, [8, 6], seed=13)

    def run():
        engine = CompiledServingEngine(
            model, params, max_batch=2, max_seq=48, decode_block=4,
            sample="categorical", temperature=0.8,
            rng=jax.random.PRNGKey(42))
        return engine.run([Request(rid=i, prompt=p, max_new_tokens=5)
                           for i, p in enumerate(prompts)])

    a, b = run(), run()
    assert a == b
    assert all(len(v) == 5 for v in a.values())
    assert all(0 <= t < cfg.vocab_size for v in a.values() for t in v)


@pytest.mark.parametrize("arch", ["minicpm3-4b", "zamba2-7b"])
def test_padded_prefill_exact_remaining_cache_families(arch):
    """The engine tests cover dense, SSM, and sliding-window bucketed
    prefill end-to-end; this pins the remaining cache families (MLA
    latent, hybrid shared-attn + mamba): padded prefill with length= must
    match unpadded to float-reassociation tolerance (padding introduces
    no new VALUES, but XLA may re-group the same sums) and be
    token-exact through decode."""
    import numpy as np
    cfg, model, params = _setup(arch)
    S, P, max_seq = 9, 16, 48
    prompt = _prompts(cfg, [S], seed=17)[0][None, :]
    padded = jnp.pad(prompt, ((0, 0), (0, P - S)))
    lu, cu = model.prefill(params, prompt, cache_len=max_seq)
    lp, cp = jax.jit(
        lambda p, t, L: model.prefill(p, t, cache_len=max_seq, length=L))(
            params, padded, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(lu), np.asarray(lp), atol=1e-5,
                               rtol=1e-5)
    assert int(jnp.argmax(lu)) == int(jnp.argmax(lp))
    tok = jnp.argmax(lu, -1)[:, None].astype(jnp.int32)
    for i in range(3):
        l_u, cu = model.decode(params, cu, tok, jnp.array([S + i]))
        l_p, cp = model.decode(params, cp, tok, jnp.array([S + i]))
        assert int(jnp.argmax(l_u)) == int(jnp.argmax(l_p)), (arch, i)
        tok = jnp.argmax(l_u, -1)[:, None].astype(jnp.int32)


def test_decode_state_shardings_places_slots_on_data():
    """The multi-host placement helper: dense cache leaves sharded on
    their cache_batch_dim, slot vectors (and block tables) on the batch
    dim, rng replicated."""
    cfg, model, params = _setup("internlm2-1.8b")
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    engine = CompiledServingEngine(model, params, max_batch=8, max_seq=32,
                                   kv_layout="dense")
    sh = decode_state_shardings(mesh, engine.state)
    P = jax.sharding.PartitionSpec
    assert sh.tokens.spec == P("data") and sh.remaining.spec == P("data")
    assert sh.rng.spec == P()
    flat = jax.tree_util.tree_flatten(sh.cache)[0]
    assert flat and all(s.mesh == mesh for s in flat)
    # the stacked-units KV leaf carries units first, slots second
    k = engine.state.cache["units"]["0"]["a"]["k"]
    k_sh = jax.tree_util.tree_flatten(sh.cache["units"]["0"]["a"])[0][0]
    assert k.shape[1] == 8
    assert k_sh.spec == P(*([None, "data"] + [None] * (k.ndim - 2)))


def test_decode_state_shardings_places_pages_on_data():
    """Paged layout: pool leaves shard their PAGE dim (page_pool_dim) on
    data — pages, not slots, are the unit of resident KV state — and the
    block tables shard like every other per-slot vector."""
    cfg, model, params = _setup("internlm2-1.8b")
    mesh = jax.make_mesh((8, 1), ("data", "model"))
    engine = CompiledServingEngine(model, params, max_batch=8, max_seq=32,
                                   kv_layout="paged", page_size=16,
                                   n_pages=16)
    assert engine.kv_layout == "paged"
    sh = decode_state_shardings(mesh, engine.state)
    P = jax.sharding.PartitionSpec
    assert sh.block_tables.spec == P("data", None)
    pool = engine.state.cache["units"]["0"]["p"]["k"]
    pool_sh = jax.tree_util.tree_flatten(sh.cache["units"]["0"]["p"])[0][0]
    # (n_units, n_pages, page_size, KVH, Dh): pages on data, rest local
    assert pool.shape[1:3] == (16, 16)
    assert pool_sh.spec == P(*([None, "data"] + [None] * (pool.ndim - 2)))


def test_oversize_prompt_rejected_clearly():
    """A prompt longer than max_seq can never fit the engine cache; both
    engines must reject it at submit() with a clear error instead of an
    opaque XLA shape failure inside the admission scatter."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompt = _prompts(cfg, [30], seed=19)[0]
    for cls in (CompiledServingEngine, ServingEngine):
        engine = cls(model, params, max_batch=1, max_seq=24)
        with pytest.raises(ValueError, match="cannot fit the engine cache"):
            engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))


def test_default_buckets_shape():
    assert default_buckets(256) == (16, 32, 64, 128, 256)
    assert default_buckets(96) == (16, 32, 64, 96)
    assert default_buckets(16) == (16,)


# ---------------------------------------------------------------------------
# prefill bucket capping (regression: silent per-length recompiles)
# ---------------------------------------------------------------------------

def test_capped_buckets_complete_to_max_seq_and_count_compiles():
    """Custom buckets capped below max_seq used to fall back to
    EXACT-LENGTH prefill for longer prompts — one silent compile per
    distinct prompt length, never counted in stats['prefill_compiles'].
    Construction must append max_seq to the bucket set, and every
    post-warmup prefill compile must be counted."""
    cfg, model, params = _setup("internlm2-1.8b")
    engine = CompiledServingEngine(model, params, max_batch=1, max_seq=32,
                                   decode_block=2, prefill_buckets=(8,))
    assert engine.buckets == (8, 32)
    for L in (5, 9, 11, 13):           # 3 distinct lengths above bucket 8
        engine.run([Request(rid=L, prompt=_prompts(cfg, [L], seed=L)[0],
                            max_new_tokens=2)])
    # 2 bucket programs total — NOT 1 + one per distinct long length
    assert engine.stats["prefill_compiles"] == 2
    # buckets beyond max_seq are dropped, not compiled
    e2 = CompiledServingEngine(model, params, max_batch=1, max_seq=32,
                               prefill_buckets=(8, 64, 128))
    assert e2.buckets == (8, 32)


def test_warmup_counts_each_bucket_once():
    cfg, model, params = _setup("internlm2-1.8b")
    engine = CompiledServingEngine(model, params, max_batch=1, max_seq=32,
                                   decode_block=2, prefill_buckets=(8, 16))
    engine.warmup()
    assert engine.stats["prefill_compiles"] == len(engine.buckets) == 3
    engine.run([Request(rid=0, prompt=_prompts(cfg, [9], seed=2)[0],
                        max_new_tokens=2)])
    # serving reuses warmed buckets: no new compiles counted
    assert engine.stats["prefill_compiles"] == 3


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------

def test_kv_layout_auto_resolution():
    """auto -> paged iff the model has pageable (full-attention GQA)
    layers; explicitly requesting paged on a pool-less model is an error,
    not a silent dense fallback."""
    _, attn_model, attn_params = _setup("internlm2-1.8b")
    _, ssm_model, ssm_params = _setup("mamba2-2.7b")
    e = CompiledServingEngine(attn_model, attn_params, max_seq=32)
    assert e.kv_layout == "paged" and e.state.block_tables.shape == (4, 2)
    e = CompiledServingEngine(ssm_model, ssm_params, max_seq=32)
    assert e.kv_layout == "dense" and e.state.block_tables.shape == (4, 0)
    with pytest.raises(ValueError, match="pageable"):
        CompiledServingEngine(ssm_model, ssm_params, max_seq=32,
                              kv_layout="paged")


def _run_engine(model, params, reqs, **kw):
    engine = CompiledServingEngine(model, params, **kw)
    out = engine.run(reqs)
    return out, engine


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "gemma3-1b",
                                  "zamba2-7b"])
def test_paged_matches_dense_across_cache_families(arch):
    """Tentpole exactness: the paged engine's tokens are identical to the
    dense engine's on every pageable family — pure GQA, mixed
    sliding-window + global (only globals paged), and hybrid shared-attn
    over mamba (only the shared block paged)."""
    cfg, model, params = _setup(arch)
    lengths = [9, 17, 5, 12, 8]
    mk = lambda: [Request(rid=i, prompt=p, max_new_tokens=6)  # noqa: E731
                  for i, p in enumerate(_prompts(cfg, lengths))]
    dense, _ = _run_engine(model, params, mk(), max_batch=2, max_seq=64,
                           decode_block=4, kv_layout="dense")
    paged, ep = _run_engine(model, params, mk(), max_batch=2, max_seq=64,
                            decode_block=4, kv_layout="paged", page_size=16)
    assert paged == dense
    assert ep.stats["decode_transfers"] == ep.stats["decode_calls"]


def test_paged_staggered_eos_and_slot_reuse_match_oracle():
    """Paged vs the per-step oracle under the adversarial schedule: late
    arrivals into reused (dirty) slots, a mid-block EOS, budgets of
    different sizes — all with page recycling in between."""
    cfg, model, params = _setup("internlm2-1.8b")
    prompts = _prompts(cfg, [9, 6, 11, 7, 5], seed=3)
    ref0 = _reference_tokens(model, params, prompts[2], 3)
    eos = ref0[2]                    # fires mid-decode for request 2
    mk = lambda: [  # noqa: E731
        Request(rid=0, prompt=prompts[0], max_new_tokens=8),
        Request(rid=1, prompt=prompts[1], max_new_tokens=3),
        Request(rid=2, prompt=prompts[2], max_new_tokens=9, eos_id=eos),
        Request(rid=3, prompt=prompts[3], max_new_tokens=7),
        Request(rid=4, prompt=prompts[4], max_new_tokens=5)]

    oracle = ServingEngine(model, params, max_batch=2, max_seq=64)
    want = oracle.run(mk())
    engine = CompiledServingEngine(model, params, max_batch=2, max_seq=64,
                                   decode_block=3, kv_layout="paged",
                                   page_size=16)
    reqs = mk()
    engine.submit(reqs[0])
    engine.submit(reqs[1])
    engine.step()
    for r in reqs[2:]:
        engine.submit(r)
        engine.step()
    steps = 0
    while (engine.active or engine.waiting) and steps < 100:
        engine.step()
        steps += 1
    for r in reqs:
        assert r.generated == want[r.rid], r.rid
    # every page returned to the pool once the workload drained
    assert len(engine._free_pages) == engine.n_pages - 1
    assert not any(engine.slot_pages)
    assert not engine._host_bt.any()


def test_paged_int8_token_exact_trio():
    """kv_cache_dtype='int8' on the paged pool: paged-int8, dense-int8
    and the int8 per-step oracle all emit identical greedy tokens (same
    per-(token, head) quantization everywhere — layout changes nothing)."""
    import dataclasses
    cfg, model, params = _setup("internlm2-1.8b")
    lengths = [9, 14, 6]
    mk = lambda: [Request(rid=i, prompt=p, max_new_tokens=6)  # noqa: E731
                  for i, p in enumerate(_prompts(cfg, lengths, seed=21))]
    paged, ep = _run_engine(model, params, mk(), max_batch=2, max_seq=64,
                            decode_block=4, kv_layout="paged",
                            kv_cache_dtype="int8")
    dense, ed = _run_engine(model, params, mk(), max_batch=2, max_seq=64,
                            decode_block=4, kv_layout="dense",
                            kv_cache_dtype="int8")
    int8_model = Model(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    oracle = ServingEngine(int8_model, params, max_batch=2, max_seq=64)
    want = oracle.run(mk())
    assert paged == dense == want
    # the int8 pool is the footprint win at equal token capacity vs the
    # f32 dense layout it replaces (int8 values + f32 per-token scales)
    f32 = CompiledServingEngine(model, params, max_batch=2, max_seq=64,
                                kv_layout="dense")
    assert ep.cache_bytes() < f32.cache_bytes()
    assert ep.stats["decode_transfers"] == ep.stats["decode_calls"]


def test_paged_small_pool_defers_admission_not_correctness():
    """A pool far smaller than slots x max_seq forces head-of-line page
    waits; tokens must still be exact and the reservation invariant means
    mid-decode growth never exhausts the pool."""
    cfg, model, params = _setup("internlm2-1.8b")
    lengths = [9, 17, 5, 12, 8]
    mk = lambda: [Request(rid=i, prompt=p, max_new_tokens=6)  # noqa: E731
                  for i, p in enumerate(_prompts(cfg, lengths))]
    dense, _ = _run_engine(model, params, mk(), max_batch=2, max_seq=64,
                           decode_block=4, kv_layout="dense")
    # 2 allocatable pages of 16 tokens vs 2 slots x 64: the 17-token
    # prompt reserves both pages, so a second request must wait
    tiny, et = _run_engine(model, params, mk(), max_batch=2, max_seq=64,
                           decode_block=4, kv_layout="paged", page_size=16,
                           n_pages=3)
    assert tiny == dense
    assert et.stats["admit_page_waits"] > 0
    assert len(et._free_pages) == et.n_pages - 1


def test_paged_rejects_unfittable_request():
    """A request whose worst case exceeds the whole pool can never admit:
    submit() must fail loudly instead of deadlocking the queue."""
    cfg, model, params = _setup("internlm2-1.8b")
    engine = CompiledServingEngine(model, params, max_batch=2, max_seq=64,
                                   kv_layout="paged", page_size=16,
                                   n_pages=3)
    with pytest.raises(ValueError, match="pages"):
        engine.submit(Request(rid=0, prompt=_prompts(cfg, [17])[0],
                              max_new_tokens=40))


def test_queue_time_from_submit_to_admission():
    """``stats['queue_s']`` sums, over admitted requests, the wait from
    ``submit`` to admission on the engine's clock; ``admissions`` counts
    them. The engine's spans record the admission, prefill, decode call
    and replay."""
    from repro import obs

    class Clock:
        now = 100.0

        def __call__(self):
            return self.now

    cfg, model, params = _setup("internlm2-1.8b")
    clock = Clock()
    engine = CompiledServingEngine(model, params, max_batch=1, max_seq=64,
                                   decode_block=4, clock=clock)
    first, second = (Request(rid=i, prompt=p, max_new_tokens=4)
                     for i, p in enumerate(_prompts(cfg, [6, 7])))
    obs.reset()
    engine.submit(first)                   # a free slot: admitted at once
    engine.submit(second)                  # waits for the only slot
    assert engine.stats["admissions"] == 1
    assert engine.stats["queue_s"] == 0.0
    clock.now += 2.5
    while engine.active or engine.waiting:
        engine.step()
    assert engine.stats["admissions"] == 2
    assert engine.stats["queue_s"] == 2.5
    assert obs.stats("serve.admit")["count"] == 2
    assert obs.stats("serve.prefill")["count"] == 2
    calls = engine.stats["decode_calls"]
    assert obs.stats("serve.decode")["count"] == calls > 0
    assert obs.stats("serve.replay")["count"] == calls
    assert engine.publish(params) is True  # no request pins the other buffer
    assert obs.stats("serve.publish_swap")["count"] == 1
    obs.reset()
