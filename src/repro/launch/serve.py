"""Batched serving driver: prefill a batch of prompts, decode N tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
      [--engine {loop,compiled}] [--batch 8] [--prompt-len 64] \
      [--new-tokens 32] [--ckpt model.ckpt]

Two decode engines:
  * ``loop`` — one jitted decode dispatch per Python iteration (the
    pre-compiled-engine baseline).
  * ``compiled`` — the whole decode fused in ONE jit (``lax.scan`` over
    steps, like repro.serve.compiled): a single bulk host transfer of the
    (B, new_tokens) block instead of per-step dispatch.

Throughput is reported for prefill and decode SEPARATELY (prompt tok/s vs
generated tok/s) plus an overall rate that includes prefill cost — the old
single ``tokens_per_s`` silently excluded prefill from throughput claims.

Live-following mode — the consumer half of the continuous train→serve
loop (``repro.serve.publish``):

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --follow ckpts/ [--follow-timeout 10]

tails ``ckpts/`` for atomic publish snapshots written by a
``WeightPublisher`` (e.g. a training run with live publishing enabled),
hot-swaps each new weight generation into a running
``CompiledServingEngine`` without dropping in-flight requests, and serves
a continuous synthetic request stream until no new generation appears for
``--follow-timeout`` seconds.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.io import load_pytree
from repro.configs import registry
from repro.dist.config import DistConfig, add_dist_args
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model


def _decode_loop(model, params, cache, tok, S, new_tokens, greedy, rng):
    """Per-step loop (baseline engine): one jitted dispatch per token."""
    decode = jax.jit(lambda p, c, t, i: model.decode(p, c, t, i))
    tokens = []
    for i in range(new_tokens):
        tokens.append(tok)
        logits, cache = decode(params, cache, tok, S + i)
        if greedy or rng is None:
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        else:
            rng, k = jax.random.split(rng)
            tok = jax.random.categorical(k, logits)[:, None].astype(jnp.int32)
    jax.block_until_ready(tok)
    return jnp.concatenate(tokens, axis=1)


def _decode_compiled(model, params, cache, tok, S, new_tokens, greedy, rng):
    """All decode steps fused under one jit; one bulk host transfer."""
    use_rng = not greedy and rng is not None
    key0 = rng if use_rng else jax.random.PRNGKey(0)

    @jax.jit
    def fused(cache, tok, key):
        def body(carry, i):
            cache, tok, key = carry
            emit = tok[:, 0]
            logits, cache = model.decode(params, cache, tok, i)
            if use_rng:
                key, k = jax.random.split(key)
                nxt = jax.random.categorical(k, logits)[:, None]
            else:
                nxt = jnp.argmax(logits, -1)[:, None]
            return (cache, nxt.astype(jnp.int32), key), emit

        (_, _, _), toks = jax.lax.scan(
            body, (cache, tok, key), jnp.arange(S, S + new_tokens))
        return toks.T                                       # (B, new)

    out = fused(cache, tok, key0)
    jax.block_until_ready(out)
    return out


def generate(model: Model, params, prompts, new_tokens: int,
             extras=None, greedy: bool = True, rng=None,
             engine: str = "loop"):
    """Batched greedy/sampled generation. prompts: (B, S) int32.
    ``engine``: "loop" (per-step dispatch) or "compiled" (fused scan);
    both produce identical greedy tokens."""
    if engine not in ("loop", "compiled"):
        raise ValueError(f"unknown engine {engine!r}")
    extras = extras or {}
    B, S = prompts.shape
    cache_len = S + new_tokens
    prefill = jax.jit(lambda p, t: model.prefill(p, t, cache_len=cache_len,
                                                 **extras))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    decode = _decode_compiled if engine == "compiled" else _decode_loop
    t0 = time.perf_counter()
    out = decode(model, params, cache, tok, S, new_tokens, greedy, rng)
    t_decode = time.perf_counter() - t0

    gen = B * new_tokens
    total = t_prefill + t_decode
    return out, {
        "engine": engine,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        # split rates: prompt tokens through prefill, generated through
        # decode — and an overall rate that does NOT hide prefill cost
        "prefill_tokens_per_s": B * S / max(t_prefill, 1e-9),
        "decode_tokens_per_s": gen / max(t_decode, 1e-9),
        "tokens_per_s": gen / max(total, 1e-9),
    }


def follow(model: Model, cfg, params, args) -> dict:
    """Serve a continuous synthetic request stream while tailing
    ``args.follow`` for publish snapshots; hot-swap each new weight
    generation into the live engine without dropping in-flight requests.

    Exits after ``--follow-timeout`` seconds with no new generation (the
    deadline resets on every pickup). Returns a per-generation report.
    """
    from repro.serve.compiled import CompiledServingEngine
    from repro.serve.engine import Request
    from repro.serve.publish import PublishFollower

    max_seq = args.prompt_len + args.new_tokens + 8
    engine = CompiledServingEngine(
        model, params, max_batch=args.batch, max_seq=max_seq,
        decode_block=args.decode_block, prefill_buckets=[args.prompt_len],
        kv_layout=args.kv_layout, page_size=args.page_size,
        admit_timeout_s=args.admit_timeout or None,
        dist=args.dist if args.dist.mesh_shape else None)
    follower = PublishFollower(args.follow, template=params)
    upd = follower.poll()
    if upd is not None:                       # seed from the newest publish
        gen, new = upd
        engine.publish(new, generation=gen)
        print(f"seeded from publish generation {gen}")
    engine.warmup(dual=True)                  # compile both decode programs

    key = jax.random.PRNGKey(args.seed + 1)
    rid = 0
    requests: list = []

    def _feed():
        """Keep every slot busy so swaps land on a loaded engine."""
        nonlocal rid
        while len(engine.waiting) + engine.active < args.batch:
            prompt = jax.random.randint(
                jax.random.fold_in(key, rid), (args.prompt_len,), 0,
                cfg.vocab_size, dtype=jnp.int32)
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=args.new_tokens)
            requests.append(req)
            engine.submit(req)
            rid += 1

    pickups = 0
    deadline = time.time() + args.follow_timeout
    while time.time() < deadline:
        upd = follower.poll()
        if upd is not None:
            gen, new = upd
            engine.publish(new, generation=gen)
            applied = "applied" if engine.generation == gen else "deferred"
            print(f"picked up generation {gen} ({applied}); "
                  f"{engine.active} requests in flight")
            pickups += 1
            deadline = time.time() + args.follow_timeout
        _feed()
        engine.step()
    while engine.active or engine.waiting:    # finish what was admitted
        engine.step()

    per_gen: dict = {}
    for req in requests:
        if req.done:
            e = per_gen.setdefault(req.generation, {"requests": 0,
                                                    "tokens": 0})
            e["requests"] += 1
            e["tokens"] += len(req.generated)
    st = engine.stats
    assert st["decode_transfers"] == st["decode_calls"], \
        "publish broke the single-transfer-per-decode-call invariant"
    return {"pickups": pickups, "per_generation": per_gen, "stats": st}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    choices=registry.list_archs())
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--engine", default="compiled",
                    choices=["loop", "compiled"],
                    help="decode engine: fused-scan (compiled) or the "
                         "per-step python loop baseline")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantized KV cache (4x tokens per cache "
                         "byte vs f32)")
    ap.add_argument("--kv-layout", default="auto",
                    choices=["auto", "dense", "paged"],
                    help="compiled-engine KV layout in --follow mode: "
                         "paged allocates cache pages on demand from a "
                         "shared pool (auto = paged when the arch "
                         "supports it)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page for --kv-layout paged")
    ap.add_argument("--follow", default="",
                    help="live-follow a publish directory: hot-swap new "
                         "weight generations into a running engine while "
                         "serving (see repro.serve.publish)")
    ap.add_argument("--follow-timeout", type=float, default=10.0,
                    help="exit --follow mode after this many seconds "
                         "without a new generation")
    ap.add_argument("--decode-block", type=int, default=4,
                    help="fused decode steps per host call in --follow")
    ap.add_argument("--admit-timeout", type=float, default=0.0,
                    help="bound (seconds) on how long a request may wait "
                         "for admission before being rejected instead of "
                         "holding the queue on an exhausted page pool "
                         "(0 = wait indefinitely)")
    add_dist_args(ap)
    args = ap.parse_args()
    args.dist = DistConfig.from_args(args)
    args.dist.initialize()
    enable_compile_cache()
    if args.dump_dist_config:
        args.dist.to_json(args.dump_dist_config)
        print(f"wrote resolved DistConfig to {args.dump_dist_config}")

    cfg = (registry.get_config(args.arch) if args.full
           else registry.get_smoke_config(args.arch))
    if args.kv_int8:
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    model = Model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    if args.ckpt:
        params = load_pytree(args.ckpt, params)
        print(f"restored {args.ckpt}")

    if args.follow:
        report = follow(model, cfg, params, args)
        print(f"follow mode done: {report['pickups']} generation pickups")
        for gen in sorted(report["per_generation"]):
            e = report["per_generation"][gen]
            print(f"  generation {gen}: {e['requests']} requests, "
                  f"{e['tokens']} tokens")
        st = report["stats"]
        print(f"decode_calls={st['decode_calls']} "
              f"decode_transfers={st['decode_transfers']} "
              f"publish_swaps={st['publish_swaps']} "
              f"dual_decode_calls={st['dual_decode_calls']}")
        return

    B = args.batch
    prompts = jax.random.randint(key, (B, args.prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    extras = {}
    if cfg.family == "vlm":
        extras["vision_embeds"] = jax.random.normal(
            key, (B, cfg.n_vision_tokens, cfg.d_model), model.dtype)
    if cfg.family == "audio":
        extras["frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), model.dtype)

    out, stats = generate(model, params, prompts, args.new_tokens,
                          extras=extras, engine=args.engine)
    print(f"arch={cfg.name} engine={args.engine} batch={B} "
          f"prompt={args.prompt_len} new={args.new_tokens}")
    print(f"prefill {stats['prefill_s']*1e3:.1f} ms "
          f"({stats['prefill_tokens_per_s']:.1f} prompt tok/s), decode "
          f"{stats['decode_s']*1e3:.1f} ms "
          f"({stats['decode_tokens_per_s']:.1f} tok/s), overall "
          f"{stats['tokens_per_s']:.1f} tok/s incl. prefill")
    print("first sequences:", out[:2, :16].tolist())


if __name__ == "__main__":
    main()
