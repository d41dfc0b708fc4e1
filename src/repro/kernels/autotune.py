"""Tile selection for the clearing kernels: pad, don't degrade.

A tile whose MB had to *divide* M would leave a prime or odd ensemble size
at MB=1 — one market per grid cell, an 8× sublane under-utilization on
TPU. So the market axis is padded instead:

  * :func:`auto_tile` always returns a sublane-aligned tile (MB a multiple
    of 8) and the padded ensemble size ``m_padded`` that makes the grid
    exact. The kernel wrappers pad the market axis with benign zero rows
    (markets are row-independent, so real rows are bitwise unaffected) and
    slice the outputs back — M=63 runs the identical tile shape as M=64.
  * :func:`autotune_tile` optionally *sweeps* (MB, agent-chunk,
    level-tile) candidates by compiling and timing each on first use,
    caching the winner per ``(device-kind, L, A, chunk)`` so every
    engine/runner built later in the process reuses the measured choice
    without re-sweeping. Each candidate takes the widest level tile at
    which its :func:`estimate_vmem_bytes` fits :data:`VMEM_BUDGET_BYTES`,
    and one that fits at none is pruned, all before any compile.

The agent-chunk and level-tile knobs bound the one-hot binning's
level-major [MB, Lt, Ac] VMEM intermediate (see ``bin_orders_onehot``);
f32 exact-integer adds make the chunked, tiled accumulation
bitwise-identical for any chunk and tile. A level tile is taken only
where the untiled one-hot would not fit the budget, so the L=128
ensembles (A up to 1024) sweep the (MB, agent-chunk) grid untiled.
"""
from __future__ import annotations

import re
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from repro.ops.metrics import span

SUBLANES = 8  # TPU f32 sublane count — tiles want MB ≡ 0 (mod 8)
LANES = 128   # TPU lane count — a one-hot's agent axis pads to it

#: The tile policy's VMEM budget per grid cell: Mosaic's default scoped
#: VMEM limit on a TPU v5e core. Every tile the policy picks fits it by
#: :func:`estimate_vmem_bytes`.
VMEM_BUDGET_BYTES = 16 << 20

#: [MB, L] f32 values one step of the clearing kernel holds at once (books,
#: incoming orders, the two scans, clearing and the carried state). Fitted
#: above the scoped VMEM Mosaic allocates for v5e at L=4096: 4.5-5 MiB at
#: MB=8 and 7-8 MiB at MB=16, whatever the agent chunk (PERF.md).
LEVEL_TEMPS = 40

#: An agent chunk narrower than a lane costs NARROW_TEMPS * MB * MB * L f32
#: more, whatever the level tile. Fitted above Mosaic's allocation for v5e
#: at L=4096 and Ac=64: about 5 MiB more at MB=8 and 20-25 MiB more at
#: MB=16 than the same tile at a whole lane (PERF.md).
NARROW_TEMPS = 6

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
    """Per-grid-cell VMEM working set of the clearing kernel, bytes.

    The level-major [MB, Lt, Ac] one-hot binning intermediate (Lt the
    level tile, Ac the agent chunk padded to whole lanes), plus the step's
    ``LEVEL_TEMPS`` level-wide [MB, L] values, ``NARROW_TEMPS`` · MB more
    of them where the agent chunk is narrower than a lane, and the
    per-chunk output paths (3 × [MB, chunk]); all f32. Fitted to stay
    above what Mosaic allocates on a v5e, not an allocator model: Mosaic
    streams a lane-aligned one-hot into the MXU without holding it whole,
    so the estimate over-counts those tiles.
    """
    ac = tile.agent_chunk or max(1, num_agents)
    lt = min(tile.level_tile or num_levels, num_levels)
    onehot = tile.mb * lt * pad_to_multiple(ac, LANES)
    levels = LEVEL_TEMPS * tile.mb * num_levels
    if ac < LANES:
        levels += NARROW_TEMPS * tile.mb * tile.mb * num_levels
    paths = 3 * tile.mb * max(1, chunk)
    return 4 * (onehot + levels + paths)


def fits(tile: "TileChoice", num_levels: int, num_agents: int,
         chunk: int = 1, budget: int = VMEM_BUDGET_BYTES) -> bool:
    return estimate_vmem_bytes(tile, num_levels, num_agents, chunk) <= budget


def fitting_level_tile(tile: "TileChoice", num_levels: int,
                       num_agents: int, chunk: int = 1) -> Optional[int]:
    """The widest level tile at which ``tile`` fits the budget: ``None``
    (all of L) where the untiled one-hot fits, else the widest power of
    two of at least a lane's width that does, else one lane's width (a
    tile that fits at no level tile, such as MB=16 with a 64-wide agent
    chunk at L=4096)."""
    lt = None
    while not fits(tile._replace(level_tile=lt), num_levels, num_agents,
                   chunk):
        lt = (lt or num_levels) // 2
        if lt <= LANES:
            return min(LANES, num_levels)
    return lt


def sweep_reports() -> Tuple["SweepReport", ...]:
    return tuple(_SWEEP_REPORTS)


def last_sweep_report() -> Optional["SweepReport"]:
    return _SWEEP_REPORTS[-1] if _SWEEP_REPORTS else None


class TileChoice(NamedTuple):
    """A resolved kernel tiling: grid tile, padded M, agent-chunk length,
    level tile."""

    mb: int                        # markets per grid cell (sublane axis)
    m_padded: int                  # M rounded up to a multiple of mb
    agent_chunk: Optional[int]     # one-hot binning chunk (None = all of A)
    level_tile: Optional[int] = None   # one-hot level tile (None = all of L)

    @property
    def grid(self) -> int:
        return self.m_padded // self.mb


def pad_to_multiple(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def default_agent_chunk(num_agents: int) -> Optional[int]:
    """Bound the [MB, L, Ac] one-hot intermediate; small A stays unchunked."""
    return 128 if num_agents > 128 else None


def auto_tile(num_markets: int, num_agents: int = 0,
              target: int = SUBLANES) -> TileChoice:
    """Heuristic sublane-aligned tile: pad M up instead of shrinking MB.

    Any M maps to MB=``target`` with ``ceil(M/target)`` grid cells — the
    tile *shape* depends only on ``target``, never on M's divisors. The
    runner gives it the widest level tile that fits the VMEM budget
    (:func:`fitting_level_tile`), so the heuristic fits at any L.
    """
    mb = max(1, target)
    return TileChoice(mb=mb, m_padded=pad_to_multiple(max(1, num_markets), mb),
                      agent_chunk=default_agent_chunk(num_agents))


def candidate_tiles(num_markets: int, num_agents: int,
                    target: int = SUBLANES,
                    agent_chunk: Optional[int] = ...,
                    num_levels: Optional[int] = None,
                    chunk: int = 1) -> List[TileChoice]:
    """The (MB, agent-chunk) sweep grid for :func:`autotune_tile`.

    An explicit ``agent_chunk`` (including ``None`` = unchunked) pins that
    knob and sweeps MB only — a caller-set VMEM bound must never be
    overridden by the sweep. Given ``num_levels``, each pair takes its
    widest fitting level tile (:func:`fitting_level_tile`), and a pair
    that fits the VMEM budget at none is left out, so nothing over the
    budget is ever compiled.
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
            tile = TileChoice(
                mb=mb, m_padded=pad_to_multiple(max(1, num_markets), mb),
                agent_chunk=None if ac >= num_agents else ac)
            if num_levels is not None:
                tile = tile._replace(level_tile=fitting_level_tile(
                    tile, num_levels, num_agents, chunk))
                if not fits(tile, num_levels, num_agents, chunk):
                    continue
            out.append(tile)
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
                  num_markets: Optional[int] = None,
                  pruned: int = 0) -> TileChoice:
    """Measure each candidate once (first compile), cache the winner.

    ``time_candidate`` compiles + runs one representative chunk call and
    returns its wall time. ``pruned`` (the candidates
    :func:`candidate_tiles` left out as over the VMEM budget) is recorded
    on the sweep's span. An out-of-memory-shaped failure
    (:func:`is_oom_error`: the tile does not fit) disqualifies the
    candidate; any other exception, such as a kernel the compiler rejects,
    propagates. If every candidate runs out of memory, ``fallback`` (the
    caller's heuristic choice) is used.
    Cached winners are re-padded for the caller's ``num_markets`` — only
    (mb, agent_chunk, level_tile) is reused across ensemble sizes.
    """
    cached = _TUNE_CACHE.get(key)
    if cached is None:
        best, best_t = None, float("inf")
        failures = []
        with span("kinetic.open.autotune", candidates=len(cands),
                  pruned=pruned):
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
