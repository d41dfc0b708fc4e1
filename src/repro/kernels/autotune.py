"""Tile selection for the persistent clearing kernels: pad, don't degrade.

The seed's ``pick_tile`` required ``mb`` to *divide* M, so a prime or odd
ensemble size degraded to MB=1 — one market per grid cell, an 8× sublane
under-utilization on TPU. This module replaces that policy:

  * :func:`auto_tile` always returns a sublane-aligned tile (MB a multiple
    of 8) and the padded ensemble size ``m_padded`` that makes the grid
    exact. The kernel wrappers pad the market axis with benign zero rows
    (markets are row-independent, so real rows are bitwise unaffected) and
    slice the outputs back — M=63 runs the identical tile shape as M=64.
  * :func:`autotune_tile` optionally *sweeps* (MB, agent-chunk) candidates
    by compiling and timing each on first use, caching the winner per
    ``(device-kind, L, A, chunk)`` so every engine/runner built later in
    the process reuses the measured choice without re-sweeping.

The agent-chunk knob bounds the one-hot binning's [MB, Ac, L] VMEM
intermediate (see ``bin_orders_onehot``); f32 exact-integer adds make the
chunked accumulation bitwise-identical for any chunk size.
"""
from __future__ import annotations

import re
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

SUBLANES = 8  # TPU f32 sublane count — tiles want MB ≡ 0 (mod 8)

#: Winner cache for the timed sweep: (device_kind, L, A, chunk) -> TileChoice.
_TUNE_CACHE: Dict[Tuple[str, int, int, int], "TileChoice"] = {}

#: One record per *real* sweep (cache misses only), newest last — the
#: ops/chaos harness reads these to assert an OOM-shaped sweep fell back.
_SWEEP_REPORTS: List["SweepReport"] = []

# Identifies an out-of-memory-shaped backend failure. XLA spells device OOM
# "RESOURCE_EXHAUSTED"; Mosaic VMEM overflows mention VMEM.
_OOM_PATTERN = re.compile(r"resource_exhausted|out of memory|\boom\b|vmem")


class SweepReport(NamedTuple):
    """Outcome of one autotune sweep (for observability + chaos tests)."""

    key: Tuple                     # the _TUNE_CACHE key that was populated
    winner: "TileChoice"           # the cached choice (fallback when fell_back)
    fell_back: bool                # True iff every candidate ran out of memory
    tried: Tuple["TileChoice", ...]
    failures: Tuple[str, ...]      # one "CandRepr: ExcType: msg" per OOM


def is_oom_error(exc: BaseException) -> bool:
    """Heuristic: does this exception look like a device/VMEM OOM?"""
    return bool(_OOM_PATTERN.search(f"{type(exc).__name__}: {exc}".lower()))


def estimate_vmem_bytes(tile: "TileChoice", num_levels: int,
                        num_agents: int, chunk: int = 1) -> int:
    """Rough per-grid-cell VMEM working set of the clearing kernel, bytes.

    Dominated by the [MB, Ac, L] one-hot binning intermediate, plus the
    resident books/profiles (6 × [MB, L]) and the per-chunk output paths
    (3 × [MB, chunk]); all f32. An estimate for dashboards and tile-pressure
    gauges, not a lowering-accurate allocator model.
    """
    ac = tile.agent_chunk or max(1, num_agents)
    onehot = tile.mb * ac * num_levels
    books = 6 * tile.mb * num_levels
    paths = 3 * tile.mb * max(1, chunk)
    return 4 * (onehot + books + paths)


def sweep_reports() -> Tuple["SweepReport", ...]:
    return tuple(_SWEEP_REPORTS)


def last_sweep_report() -> Optional["SweepReport"]:
    return _SWEEP_REPORTS[-1] if _SWEEP_REPORTS else None


class TileChoice(NamedTuple):
    """A resolved kernel tiling: grid tile, padded M, agent-chunk length."""

    mb: int                        # markets per grid cell (sublane axis)
    m_padded: int                  # M rounded up to a multiple of mb
    agent_chunk: Optional[int]     # one-hot binning chunk (None = all of A)

    @property
    def grid(self) -> int:
        return self.m_padded // self.mb


def pad_to_multiple(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def default_agent_chunk(num_agents: int) -> Optional[int]:
    """Bound the [MB, Ac, L] one-hot intermediate; small A stays unchunked."""
    return 128 if num_agents > 128 else None


def auto_tile(num_markets: int, num_agents: int = 0,
              target: int = SUBLANES) -> TileChoice:
    """Heuristic sublane-aligned tile: pad M up instead of shrinking MB.

    Any M maps to MB=``target`` with ``ceil(M/target)`` grid cells — the
    tile *shape* depends only on ``target``, never on M's divisors.
    """
    mb = max(1, target)
    return TileChoice(mb=mb, m_padded=pad_to_multiple(max(1, num_markets), mb),
                      agent_chunk=default_agent_chunk(num_agents))


def candidate_tiles(num_markets: int, num_agents: int,
                    target: int = SUBLANES,
                    agent_chunk: Optional[int] = ...) -> List[TileChoice]:
    """The (MB, agent-chunk) sweep grid for :func:`autotune_tile`.

    An explicit ``agent_chunk`` (including ``None`` = unchunked) pins that
    knob and sweeps MB only — a caller-set VMEM bound must never be
    overridden by the sweep.
    """
    mbs = sorted({target, 2 * target})
    if agent_chunk is not ...:
        acs = [agent_chunk if agent_chunk else num_agents]
    else:
        acs = sorted({c for c in (64, 128, num_agents)
                      if 0 < c <= num_agents}) or [num_agents]
    out = []
    for mb in mbs:
        for ac in acs:
            out.append(TileChoice(
                mb=mb, m_padded=pad_to_multiple(max(1, num_markets), mb),
                agent_chunk=None if ac >= num_agents else ac))
    # dedup while keeping sweep order deterministic
    seen, uniq = set(), []
    for c in out:
        if c not in seen:
            seen.add(c)
            uniq.append(c)
    return uniq


def tune_key(num_levels: int, num_agents: int, chunk: int,
             **context) -> Tuple:
    """Winner cache key: (device-kind, L, A, chunk) plus any ``context``
    that changes what is being timed (kernel family, stats_only,
    a pinned agent_chunk) — distinct kernel configurations must never share
    a measured winner."""
    import jax

    return ((jax.devices()[0].device_kind, num_levels, num_agents, chunk)
            + tuple(sorted(context.items())))


def autotune_tile(key: Tuple,
                  time_candidate: Callable[[TileChoice], float],
                  cands: List[TileChoice],
                  fallback: Optional[TileChoice] = None,
                  num_markets: Optional[int] = None) -> TileChoice:
    """Measure each candidate once (first compile), cache the winner.

    ``time_candidate`` compiles + runs one representative chunk call and
    returns its wall time. An out-of-memory-shaped failure
    (:func:`is_oom_error`: the tile does not fit) disqualifies the
    candidate; any other exception, such as a kernel the compiler rejects,
    propagates. If every candidate runs out of memory, ``fallback`` (the
    caller's heuristic choice) is used.
    Cached winners are re-padded for the caller's ``num_markets`` — only
    (mb, agent_chunk) is reused across ensemble sizes.
    """
    cached = _TUNE_CACHE.get(key)
    if cached is None:
        best, best_t = None, float("inf")
        failures = []
        for cand in cands:
            try:
                t = time_candidate(cand)
            except Exception as exc:
                if not is_oom_error(exc):
                    raise
                failures.append(f"{cand!r}: {type(exc).__name__}: {exc}")
                continue
            if t < best_t:
                best, best_t = cand, t
        fell_back = best is None
        if fell_back:  # every candidate failed: the heuristic choice
            best = fallback if fallback is not None else auto_tile(
                num_markets or 1)
        _TUNE_CACHE[key] = cached = best
        _SWEEP_REPORTS.append(SweepReport(
            key=key, winner=best, fell_back=fell_back, tried=tuple(cands),
            failures=tuple(failures)))
    if num_markets is not None:
        cached = cached._replace(
            m_padded=pad_to_multiple(max(1, num_markets), cached.mb))
    return cached


def time_call(fn: Callable[[], object], block: Callable[[object], None],
              trials: int = 2) -> float:
    """Best-of-``trials`` wall time of ``fn`` after one warmup/compile call."""
    block(fn())
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        block(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def clear_tune_cache() -> None:
    _TUNE_CACHE.clear()
    _SWEEP_REPORTS.clear()
