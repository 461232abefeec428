"""Open-loop serving on one chip through the program's compiled engine:
``CompiledServingEngine.submit`` / ``step``, greedy, paged KV cache.

Set-up makes the weights (the benchmark's, from the seed, in the served
dtype) and the prompts on the device, builds the engine, and warms exactly
the programs the window will use: one prefill per prompt bucket, the
admission scatter and the fused decode block (by serving one request per
bucket through ``submit``/``step``), and the host-side pad of every prompt
length in the schedule. The window offers the schedule of
``chipbench.openloop`` at its due times and steps the engine until every
request is done (at most ``drain_seconds`` past the window).

``correct``: a sample of finished requests drawn from the seed, always with
the longest, and at least ``check_tokens`` served tokens in all. Once the
engine is freed, the plain reference runs its full forward over each
prompt with its served tokens; for each served token the gap by which its
logit lies below the reference's best at that position. The widest gap is
the number compared.

Traffic keys: ``rate`` (requests/s), ``shape_seed``, ``prompt`` and
``output`` (lognormal ``median``, ``sigma``, ``min``, ``max``),
``max_total``, ``markov_states``, ``engine`` (``max_batch``, ``max_seq``,
``decode_block``, ``page_size``, ``kv_cache_dtype``, ``buckets``),
``drain_seconds``, ``trace_seconds``, ``check_requests``, ``check_tokens``.
"""
from __future__ import annotations

import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(f"[serve_open_loop] {msg}", file=sys.stderr, flush=True)


def build(run, params):
    from chipbench import harness
    from repro.models.model import Model
    from repro.serve.compiled import CompiledServingEngine

    e = run.traffic["engine"]
    return CompiledServingEngine(
        Model(harness.program_config(run.config)), params,
        max_batch=e["max_batch"], max_seq=e["max_seq"],
        decode_block=e["decode_block"], prefill_buckets=e["buckets"],
        sample="greedy", kv_layout="paged", page_size=e["page_size"],
        kv_cache_dtype=e["kv_cache_dtype"])


def warm(engine, prompts, lengths, Request) -> None:
    """Compile what the window runs, through the engine's own calls."""
    import jax.numpy as jnp
    used = sorted({engine._bucket(n) for n in lengths})
    warmups = [Request(rid=-1 - i,
                       prompt=prompts[i, :min(b, prompts.shape[1])],
                       max_new_tokens=2 * engine.decode_block + 1)
               for i, b in enumerate(used)]
    engine.run(warmups)
    for n in sorted(set(lengths)):      # the admission's host-side pad
        b = engine._bucket(n)
        jnp.pad(prompts[0, :n][None, :].astype(jnp.int32),
                ((0, 0), (0, b - n))).block_until_ready()


def sample_checked(rng, done: list, min_tokens: int, max_requests: int):
    """Indices into ``done`` ((rid, prompt_len, n_out) tuples): the longest
    first, then a seeded draw until ``min_tokens`` served tokens."""
    order = sorted(range(len(done)), key=lambda i: -(done[i][1] + done[i][2]))
    picked = [order[0]]
    rest = [i for i in rng.permutation(len(done)) if i != order[0]]
    for i in rest:
        if (sum(done[j][2] for j in picked) >= min_tokens
                or len(picked) >= max_requests):
            break
        picked.append(int(i))
    return picked


def reference_gaps(ref, cfg, params, seqs, control: bool = False):
    """Widest gap of the served tokens (and, with ``control``, of the
    tokens the float8 reference puts first) under the float32 reference.
    ``seqs``: list of (prompt + served tokens, prompt length, served)."""
    import jax
    import jax.numpy as jnp
    T = max(len(s) for s, _, _ in seqs)
    T = -(-T // 1024) * 1024
    R = len(seqs)
    tokens = np.zeros((R, T), np.int32)
    served = np.zeros((R, T, 1), np.int32)
    mask = np.zeros((R, T), bool)
    for r, (s, lp, out) in enumerate(seqs):
        tokens[r, :len(s)] = s
        pos = np.arange(lp - 1, lp - 1 + len(out))
        served[r, pos, 0] = out
        mask[r, pos] = True
    fwd = jax.jit(lambda p, x, t, mode: ref.next_token_logits(
        p, x, t, cfg, mode), static_argnums=(3,))
    targets = served
    if control:
        _, _, best8 = fwd(params, tokens, served, "fp8")
        targets = np.concatenate([served, np.asarray(best8)[..., None]], -1)
    top, picked, _ = fwd(params, tokens, jnp.asarray(targets), "f32")
    gaps = np.asarray(top)[..., None] - np.asarray(picked)
    out = {"served_gap": float(gaps[..., 0][mask].max())}
    if control:
        out["control_gap"] = float(gaps[..., 1][mask].max())
    return out


def run(run, control: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from chipbench import harness, lmdata, openloop, weights
    from repro.models.model import Model
    from repro.serve.engine import Request

    t, cfg = run.traffic, run.config
    sched = openloop.schedule(t, run.seconds, run.seed)
    lengths = [d.prompt_len for d in sched]
    mc = harness.program_config(cfg)
    shapes = jax.eval_shape(Model(mc).init, jax.random.PRNGKey(0))
    params = weights.make(shapes, run.seed, jnp.bfloat16)
    rows = lmdata.markov_rows(run.seed, len(sched) + 1, t["prompt"]["max"],
                              mc.vocab_size, t["markov_states"])["tokens"]
    engine = build(run, params)
    del params
    warm(engine, rows, lengths, Request)
    prompts = [rows[d.rid, :d.prompt_len] for d in sched]
    requests = [Request(rid=d.rid, prompt=prompts[i],
                        max_new_tokens=d.output_len)
                for i, d in enumerate(sched)]
    jax.block_until_ready(prompts)
    stats0 = dict(engine.stats)
    setup_s = time.perf_counter() - run.t_start
    log(f"set-up {setup_s:.2f} s; {len(sched)} requests due over "
        f"{run.seconds} s; buckets {engine.buckets}")

    decode = {"n": 0, "s": 0.0}

    def on_step(seconds, admitted):
        if not admitted:
            decode["n"] += 1
            decode["s"] += seconds

    t0 = time.perf_counter()
    timings = [openloop.Timing(due=t0 + d.at) for d in sched]
    traced_until = t0 + min(t.get("trace_seconds", run.seconds), run.seconds)
    with run.traced():
        openloop.drive(engine, requests, timings, t0, traced_until - t0,
                       on_step)
    stats_traced = {k: engine.stats[k] - stats0[k] for k in stats0}
    openloop.close(timings, openloop.drive(
        engine, requests, timings, t0, run.seconds + t["drain_seconds"],
        on_step))
    run.window_bounds = (t0, time.perf_counter())
    summ = openloop.summary(timings)
    peak = harness.memory_peak(run.devices)
    log(f"generator lateness p50 {summ['late_p50_ms']:.3f} ms, max "
        f"{summ['late_max_ms']:.3f} ms; ttft p50 {summ['ttft_p50_ms']:.1f} "
        f"p95 {summ['ttft_p95_ms']:.1f} ms; tpot p50 "
        f"{summ['tpot_p50_ms']:.2f} p95 {summ['tpot_p95_ms']:.2f} ms; "
        f"{summ['failed']} failed of {summ['n']}; engine {engine.stats}")

    done = [(r.rid, int(r.prompt.shape[0]), list(r.generated))
            for r in requests if r.done and not r.rejected]
    rng = np.random.default_rng(run.seed % (1 << 63))
    picked = sample_checked(rng, [(a, b, len(c)) for a, b, c in done],
                            t["check_tokens"], t["check_requests"])
    seqs = []
    for i in picked:
        rid, lp, out = done[i]
        prompt = np.asarray(rows[rid, :lp])
        seqs.append((np.concatenate([prompt, np.asarray(out[:-1])]), lp,
                     np.asarray(out)))
    del engine, requests, prompts
    t_ref = time.perf_counter()
    params = weights.make(shapes, run.seed, jnp.bfloat16)
    gaps = reference_gaps(run.cell.reference(), cfg, params, seqs, control)
    log(f"reference {time.perf_counter() - t_ref:.1f} s over {len(seqs)} "
        f"requests, {sum(len(o) for _, _, o in seqs)} served tokens: {gaps}")
    checks = {"served_logit_gap": {"value": gaps["served_gap"],
                                   "limit": run.cell.limits[
                                       "served_logit_gap"]}}
    return {
        "metrics": {"ttft_p95_ms": summ["ttft_p95_ms"],
                    "tpot_p95_ms": summ["tpot_p95_ms"], "setup_s": setup_s},
        "attempted": summ["n"], "failed": summ["failed"],
        "memory_peak_bytes": peak, "checks": checks, "gaps": gaps,
        "summary": summ, "ttft_s": [openloop.ttft(x) for x in timings],
        "done_per_s": sum(not x.failed for x in timings) / (
            max(x.last for x in timings if not x.failed) - t0),
        "facts": {"prefills_traced": stats_traced["admissions"],
                  "decode_calls": decode["n"],
                  "decode_call_s": decode["s"]},
    }
