"""Persisted kernel-autotuning cache: design points per backend x kernel x
shape bucket.

``benchmarks/bench_kernels.py`` sweeps the design-point space (block sizes,
``num_warps``/``num_stages``) per shape bucket on a live backend, scores each
point against the ``benchmarks/roofline.py`` analytical model, and persists
winners here (``tuning_cache.json``, checked in). ``dispatch.resolve``
consults the cache at call time; a miss falls back to the deterministic
``DEFAULT_DESIGN`` so untuned shapes degrade gracefully instead of erroring.

This module is deliberately **stdlib-only** (no jax import): the CI lint job
schema-checks the cache file via ``benchmarks/check_tuning_cache.py`` on a
host with no JAX installed.

Cache schema (``tuning_cache.json``)::

    {
      "version": 1,
      "entries": {
        "<backend>/<kernel>/<bucket>": {
          "block_q": int, "block_k": int,
          "num_warps": int, "num_stages": int
        },
        ...
      }
    }

Keys are ``backend in {cpu,gpu,tpu}`` x ``kernel in KERNELS`` x the kernel's
shape bucket (``shape_bucket``). Per-kernel meaning of the fields:

  kernel           block_q            block_k      num_warps  num_stages
  -----------------------------------------------------------------------
  flash_attention  query tile rows    kv tile rows    yes        yes
  ssd              (unused, 0)        (unused, 0)     yes        yes
  swa_avg          element tile size  (unused, 0)     yes        yes

``block_*`` fields are 0 when a kernel does not use them; 0 also means
"kernel default" when a design point is pinned by hand.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from functools import lru_cache
from typing import Optional, Sequence, Tuple, Union

KERNELS = ("flash_attention", "ssd", "swa_avg")
BACKENDS = ("cpu", "gpu", "tpu")

CACHE_PATH = os.path.join(os.path.dirname(__file__), "tuning_cache.json")

KEY_RE = re.compile(
    r"^(cpu|gpu|tpu)/(flash_attention|ssd|swa_avg)/[a-z0-9_]+$")

_FIELDS = ("block_q", "block_k", "num_warps", "num_stages")
_VALID_WARPS = (1, 2, 4, 8, 16, 32)


@dataclasses.dataclass(frozen=True)
class DesignPoint:
    """One point in a kernel's tuning space. Frozen/hashable so it can ride
    through jit static args and ``custom_vjp`` nondiff argnums."""

    block_q: int = 0
    block_k: int = 0
    num_warps: int = 4
    num_stages: int = 2

    def astuple(self) -> Tuple[int, int, int, int]:
        return (self.block_q, self.block_k, self.num_warps, self.num_stages)


# Deterministic fallback when the cache has no entry for a (backend, kernel,
# bucket) key. flash blocks match the Mosaic kernel's long-standing defaults;
# swa_avg's 8192-element tile matches the TPU kernel's (8, 1024) VMEM tile.
DEFAULT_DESIGN = {
    "flash_attention": DesignPoint(block_q=128, block_k=128,
                                   num_warps=4, num_stages=2),
    "ssd": DesignPoint(block_q=0, block_k=0, num_warps=4, num_stages=2),
    "swa_avg": DesignPoint(block_q=8192, block_k=0,
                           num_warps=4, num_stages=2),
}


# The Mosaic flash kernel's (block_q, block_k) for a TPU call with no cache
# entry and no pinned design: the largest tile a TPU v5e sweep found good at
# head dim 128 (PERF.md), cut per call by ``flash_tile`` so that a short
# sequence is not padded up to it. Tile rows x padded head dim stay within
# TPU_FLASH_TILE_AREA, the most the v5e compiler placed in VMEM for the
# backward at its default limit.
TPU_FLASH_TILES = (1024, 1024)
TPU_FLASH_TILE_AREA = 1024 * 256


def flash_tile(seq_len: int, largest: int) -> int:
    """The largest power-of-two tile from 128 up to ``largest`` that
    divides ``seq_len`` rounded up to 128."""
    padded = -(-seq_len // 128) * 128
    tile = largest
    while tile > 128 and padded % tile:
        tile //= 2
    return tile


def as_design(design) -> DesignPoint:
    """Coerce a DesignPoint | 4-tuple | None-fields dict to a DesignPoint."""
    if isinstance(design, DesignPoint):
        return design
    if isinstance(design, dict):
        return DesignPoint(**{k: int(design[k]) for k in _FIELDS})
    if isinstance(design, Sequence):
        vals = tuple(int(v) for v in design)
        if len(vals) != 4:
            raise ValueError(
                f"design point tuple must be (block_q, block_k, num_warps, "
                f"num_stages); got {design!r}")
        return DesignPoint(*vals)
    raise ValueError(f"cannot interpret design point {design!r}")


def _next_pow2(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def shape_bucket(kernel: str,
                 shape: Union[Tuple[int, ...], Sequence[int]]) -> str:
    """Map a call shape to its tuning bucket (power-of-2 size classes).

    Per-kernel shape tuples:
      flash_attention: (kv_len, head_dim)
      ssd:             (seq_len, head_dim P)
      swa_avg:         (numel,)
    """
    if kernel == "flash_attention":
        skv, d = shape
        return f"skv{_next_pow2(skv)}_d{_next_pow2(d)}"
    if kernel == "ssd":
        s, p = shape
        return f"s{_next_pow2(s)}_p{_next_pow2(p)}"
    if kernel == "swa_avg":
        (numel,) = shape
        return f"n{_next_pow2(numel)}"
    raise ValueError(f"unknown kernel {kernel!r}; expected one of {KERNELS}")


@lru_cache(maxsize=None)
def _load(path: str) -> dict:
    if not os.path.exists(path):
        return {"version": 1, "entries": {}}
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "entries" not in data:
        raise ValueError(
            f"malformed tuning cache {path}: expected an object with an "
            f"'entries' key")
    return data


def load_cache(path: Optional[str] = None) -> dict:
    """Load (and memoize) the tuning cache. Missing file -> empty cache."""
    return _load(path or CACHE_PATH)


def clear_cache() -> None:
    """Drop the memoized cache (tests; after --update-cache writes)."""
    _load.cache_clear()


def _entry_errors(key: str, entry) -> list:
    errs = []
    if not KEY_RE.match(key):
        errs.append(f"key {key!r} does not match "
                    f"'backend/kernel/bucket' format ({KEY_RE.pattern})")
    if not isinstance(entry, dict):
        errs.append(f"entry {key!r} is not an object: {entry!r}")
        return errs
    for fld in _FIELDS:
        if fld not in entry:
            errs.append(f"entry {key!r} missing field {fld!r}")
        elif not isinstance(entry[fld], int) or isinstance(entry[fld], bool):
            errs.append(f"entry {key!r} field {fld!r} must be an int, got "
                        f"{entry[fld]!r}")
    extra = set(entry) - set(_FIELDS)
    if extra:
        errs.append(f"entry {key!r} has unknown fields {sorted(extra)}")
    if errs:
        return errs
    if entry["num_warps"] not in _VALID_WARPS:
        errs.append(f"entry {key!r}: num_warps {entry['num_warps']} not in "
                    f"{_VALID_WARPS}")
    if not 1 <= entry["num_stages"] <= 8:
        errs.append(f"entry {key!r}: num_stages {entry['num_stages']} "
                    f"outside [1, 8]")
    for fld in ("block_q", "block_k"):
        v = entry[fld]
        if v < 0 or (v > 0 and v & (v - 1)):
            errs.append(f"entry {key!r}: {fld} {v} must be 0 or a power "
                        f"of 2")
    return errs


def validate_cache(data: dict) -> list:
    """All schema violations in a loaded cache (empty list == valid)."""
    errs = []
    if data.get("version") != 1:
        errs.append(f"unknown cache version {data.get('version')!r}")
    entries = data.get("entries")
    if not isinstance(entries, dict):
        return errs + ["'entries' is not an object"]
    for key, entry in sorted(entries.items()):
        errs.extend(_entry_errors(key, entry))
    return errs


def lookup(backend: str, kernel: str, shape,
           path: Optional[str] = None) -> Optional[DesignPoint]:
    """Cache entry for (backend, kernel, shape's bucket), or None on miss.
    A malformed entry raises a clear ValueError naming the key rather than
    crashing downstream in a jitted trace."""
    entries = load_cache(path).get("entries", {})
    key = f"{backend}/{kernel}/{shape_bucket(kernel, shape)}"
    entry = entries.get(key)
    if entry is None:
        return None
    errs = _entry_errors(key, entry)
    if errs:
        raise ValueError(
            "malformed tuning cache entry (regenerate with "
            "benchmarks/bench_kernels.py --update-cache): "
            + "; ".join(errs))
    return DesignPoint(**{f: entry[f] for f in _FIELDS})


def design_for(backend: str, kernel: str, shape=None,
               path: Optional[str] = None) -> Tuple[DesignPoint, bool]:
    """(design point, cache_hit) — the cached winner for this shape bucket,
    or the kernel's deterministic default on miss / when no shape is given."""
    if shape is not None:
        dp = lookup(backend, kernel, shape, path=path)
        if dp is not None:
            return dp, True
    if kernel not in DEFAULT_DESIGN:
        raise ValueError(f"unknown kernel {kernel!r}; expected one of "
                         f"{KERNELS}")
    return DEFAULT_DESIGN[kernel], False


def update_entries(winners: dict, path: Optional[str] = None) -> str:
    """Merge {key: DesignPoint|dict} winners into the cache file (sorted
    keys, stable formatting) and return the path written."""
    path = path or CACHE_PATH
    data = {"version": 1, "entries": {}}
    if os.path.exists(path):
        data = load_cache(path)
    entries = dict(data.get("entries", {}))
    for key, dp in winners.items():
        dp = as_design(dp)
        entries[key] = {f: getattr(dp, f) for f in _FIELDS}
    out = {"version": 1, "entries": dict(sorted(entries.items()))}
    errs = validate_cache(out)
    if errs:
        raise ValueError("refusing to write invalid tuning cache: "
                         + "; ".join(errs))
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    clear_cache()
    return path
