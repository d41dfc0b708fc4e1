"""KineticSim persistent clearing kernel — the paper's contribution on TPU.

GPU original (paper §III): one CUDA block per market, LOB in ``__shared__``
memory for all S steps, atomicAdd order binning, Hillis–Steele scans,
tournament argmax.

TPU adaptation (DESIGN.md §2): one Pallas grid cell per *tile* of MB markets.
The entire S-step loop runs inside the kernel body; the books live in VMEM
(registers/VMEM values carried through ``lax.fori_loop``) and touch HBM only
at kernel entry/exit — HBM traffic is Θ(M·L), independent of S, exactly the
paper's claim. Order binning is a one-hot MXU contraction (the TPU-native
replacement for shared-memory atomics); clearing runs the same xp-polymorphic
``auction.clear`` / ``agents.decide`` code as every other backend, so results
are bitwise identical.

Block/tile layout: markets on sublanes (MB a multiple of 8 — the chunk
entries *pad* the market axis to a tile multiple instead of shrinking MB, so
prime/odd M keeps full sublane tiles; see :mod:`repro.kernels.autotune`),
price ticks on lanes (L multiple of 128 native; smaller L still correct,
just padded by the compiler). VMEM working set per grid cell ≈
``7·MB·L + MB·Ac·L (one-hot binning, Ac = agent_chunk ≤ A) + 2·MB·S`` f32
for path outputs, plus a negligible ``12·MB`` term for the per-market
parameter columns (the :class:`repro.core.params.MarketParams` operands,
one ``(MB, 1)`` block each) — padding adds only whole-tile rows, so the
padded-tile term is the same ``MB·(...)`` budget with ``grid =
ceil(M/MB)`` cells. In ``stats_only`` mode the ``2·MB·S`` path term is
replaced by a constant ``6·MB`` statistics-accumulator term
(count/Σmid/Σmid²/min/max/Σvolume), making both the VMEM footprint and the
HBM output traffic independent of the chunk length — see EXPERIMENTS.md
§Perf for the measured budget.

Scenario engine: archetype mixtures and scenario overlays (flash-crash
shock, volatility regimes, book seeding) are static ``cfg`` fields dispatched
branch-free inside ``simulate_step`` — every scenario traces to the same
fully fused persistent kernel, and baseline configs trace the identical
graph as before the scenario engine existed.

Sharding: the chunk entry takes an explicit per-row ``market_ids`` operand
(instead of deriving ids from the grid index), so a ``shard_map`` caller can
hand each device its true *global* market coordinates — the RNG stream is a
pure function of (seed, market id, step), which is what makes a sharded run
bitwise-identical to the single-device run. See ``repro.kernels.ops``.

Heterogeneous ensembles: every scenario-varying parameter — shock schedule
and intensities, marketable-flow probability, quantity cap, archetype
knobs, per-market population counts — enters the chunk entry as a
:class:`repro.core.params.MarketParams` operand of ``[M, 1]`` columns.
Each grid cell fetches its tile's rows (``(mb, 1)`` blocks on the sublane
axis, exactly like the ``market_ids``/``last_price`` scalars), so a single
compiled kernel serves any scenario mixture and any parameter values: only
the static shape ``(M, A, L, chunk)`` and the RNG seed are baked into the
trace. Scenario dispatch stays branch-free ``where`` selects inside
``simulate_step`` — per-market heterogeneity costs no divergence, because
there is none to diverge: the masks are just data.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.core import params as params_mod
from repro.core import stats as stats_mod
from repro.core.config import MarketConfig
from repro.core.params import EnsembleSpec, MarketParams
from repro.core.step import MarketState, resolve_peer_mids, simulate_step
from repro.kernels.autotune import pad_to_multiple

#: Number of per-market parameter operands threaded into the chunk kernels.
NUM_PARAM_OPERANDS = len(MarketParams._fields)

#: The kernels' clearing scan. Mosaic lowers the log-depth Hillis-Steele
#: scan; it has no rule for ``cumsum`` or for the ``flip`` of the suffix
#: sum. Both scans give the same bits on exact-integer books.
KERNEL_SCAN = "hillis-steele"


def pallas_call(body, *, interpret: bool, **kwargs):
    """``pl.pallas_call`` for the clearing kernels: Mosaic with a parallel
    market grid, or the interpreter for tests on a host without a TPU.

    Interpret mode is refused on a TPU backend, so no caller can time or
    ship the interpreter by mistake.
    """
    if interpret:
        if jax.default_backend() == "tpu":
            raise ValueError("interpret=True on a TPU backend: the clearing "
                             "kernels lower through Mosaic there")
    else:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    return pl.pallas_call(body, interpret=interpret, **kwargs)


def write_column(path, s, col):
    """``path`` with column ``s`` replaced by ``col`` ([mb, 1]): an
    iota-mask select, since Mosaic cannot lower ``dynamic_update_slice``
    into a VMEM value."""
    cols = jax.lax.broadcasted_iota(jnp.int32, path.shape, 1)
    return jnp.where(cols == s, col, path)


def tile_market_ids(mb: int):
    """Global market ids of grid cell ``program_id(0)``'s rows, [mb, 1]."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (mb, 1), 0)
    return pl.program_id(0) * mb + rows


def resolve_params(cfg, M: int, params: Optional[MarketParams],
                   xp) -> MarketParams:
    """The chunk entries' params operand: explicit > spec-owned > scalar
    broadcast of a legacy ``MarketConfig`` (value-identical constants)."""
    if params is not None:
        return params
    if isinstance(cfg, EnsembleSpec):
        return cfg.params.asarray(xp)
    return params_mod.params_from_config(cfg, M, xp)


def pad_params(params: MarketParams, m_padded: int) -> MarketParams:
    """Dtype-preserving zero-row padding of every parameter column (a pad
    row is a zero-count, zero-intensity market whose outputs are sliced
    off — see :func:`_pad_rows`)."""
    return MarketParams(*(
        _pad_rows(jnp.asarray(leaf, dtype=MarketParams.field_dtype(f)),
                  m_padded)
        for f, leaf in zip(MarketParams._fields, params)))


def _kernel_body(
    bid_ref, ask_ref, last_ref, pmid_ref,
    out_bid_ref, out_ask_ref, out_last_ref, out_pmid_ref,
    price_path_ref, volume_path_ref,
    *, cfg: MarketConfig, mb: int,
):
    """Persistent scheduler (paper Alg. 1) for one tile of ``mb`` markets."""
    S = cfg.num_steps

    # Phase 1: load opening books into VMEM-resident values (Alg.1 lines 2-3).
    bid = bid_ref[...]
    ask = ask_ref[...]
    last = last_ref[...]
    pmid = pmid_ref[...]

    market_ids = tile_market_ids(mb)

    def body(s, carry):
        bid, ask, last, pmid, pp, vp = carry
        state = MarketState(bid=bid, ask=ask, last_price=last, prev_mid=pmid)
        # Phases 2-5 (Alg.1 lines 5-22): shared semantics, one-hot binning.
        new_state, out = simulate_step(
            cfg, state, s, market_ids, jnp, bin_orders=None, scan=KERNEL_SCAN
        )
        pp = write_column(pp, s, out.price)
        vp = write_column(vp, s, out.volume)
        return (new_state.bid, new_state.ask, new_state.last_price,
                new_state.prev_mid, pp, vp)

    pp0 = jnp.zeros((mb, S), jnp.float32)
    vp0 = jnp.zeros((mb, S), jnp.float32)
    bid, ask, last, pmid, pp, vp = jax.lax.fori_loop(
        0, S, body, (bid, ask, last, pmid, pp0, vp0)
    )

    # Final writeback (Alg.1 line 24) — the only per-market HBM stores.
    out_bid_ref[...] = bid
    out_ask_ref[...] = ask
    out_last_ref[...] = last
    out_pmid_ref[...] = pmid
    price_path_ref[...] = pp
    volume_path_ref[...] = vp


def pick_tile(num_markets: int, target: int = 8) -> int:
    """Largest divisor of M that is <= target (sublane-aligned when possible).

    Legacy policy for the exact-grid one-shot entries (`kinetic_clearing`,
    `naive_clearing`): prime/odd M degrades to MB=1. The session chunk
    entries instead pad the market axis and keep full sublane tiles — see
    :func:`repro.kernels.autotune.auto_tile`.
    """
    mb = min(target, num_markets)
    while num_markets % mb:
        mb -= 1
    return mb


def _pad_rows(x, m_padded: int):
    """Append zero rows up to ``m_padded`` (markets are row-independent, so
    benign zero-book pad rows never perturb real rows — branch-free mask by
    construction; the wrapper slices them off every output)."""
    pad = m_padded - x.shape[0]
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)


def _chunk_kernel_body(
    step0_ref, nvalid_ref, mids_ref,
    bid_ref, ask_ref, last_ref, pmid_ref, ext_buy_ref, ext_ask_ref,
    peer_ref,
    *refs,
    cfg, mb: int, chunk: int,
    agent_chunk: Optional[int], stats_only: bool,
):
    """Session variant of the persistent scheduler: a fixed ``chunk``-length
    trace that serves *any* requested step count and *any* scenario mixture.

    ``step0`` (runtime scalar) offsets the RNG / scenario step coordinate so
    a warm session resumes mid-stream; ``n_valid`` (runtime scalar) gates the
    carried state with branch-free ``where`` masks so a partial tail chunk
    advances exactly ``n_valid`` steps without retracing. External orders
    (``ext_buy``/``ext_ask``, the RL stepping hook's reserved slot) are
    injected at the first local step only; zero arrays are bitwise no-ops.

    ``peer_ref`` is the coupling column: each row's *peer mid*, gathered by
    the chunk entry from the chunk-entry ``prev_mid`` (or by the sharded
    caller via the ring halo exchange) and held fixed for all ``chunk``
    steps — the freeze boundary every backend shares.

    ``mids_ref`` carries the per-row *global* market ids (sharded callers
    pass each device's true coordinates). The first ``NUM_PARAM_OPERANDS``
    of ``refs`` are the per-market :class:`MarketParams` columns — this
    tile's ``(mb, 1)`` rows of every scenario-varying knob, loaded into
    VMEM once and broadcast over the agent/level axes inside
    ``simulate_step``. In ``stats_only`` mode the per-step path outputs are
    replaced by six [mb, 1] running accumulators carried through the
    ``fori_loop`` — the kernel's HBM writes become Θ(MB·L) books plus
    Θ(MB) statistics, independent of ``chunk``.
    """
    step0 = step0_ref[0, 0]
    n_valid = nvalid_ref[0, 0]

    params = MarketParams(*(r[...] for r in refs[:NUM_PARAM_OPERANDS]))
    refs = refs[NUM_PARAM_OPERANDS:]

    if stats_only:
        (cnt_ref, smid_ref, ssq_ref, mn_ref, mx_ref, svol_ref,
         out_bid_ref, out_ask_ref, out_last_ref, out_pmid_ref,
         out_cnt_ref, out_smid_ref, out_ssq_ref, out_mn_ref, out_mx_ref,
         out_svol_ref) = refs
    else:
        (out_bid_ref, out_ask_ref, out_last_ref, out_pmid_ref,
         price_path_ref, volume_path_ref, mid_path_ref) = refs

    bid = bid_ref[...]
    ask = ask_ref[...]
    last = last_ref[...]
    pmid = pmid_ref[...]
    ext_b = ext_buy_ref[...]
    ext_a = ext_ask_ref[...]
    zeros_ext = jnp.zeros_like(ext_b)
    peer_mid = peer_ref[...]

    market_ids = mids_ref[...]
    # Step-invariant type lattice, hoisted out of the fori_loop.
    atype = params_mod.agent_types(params, cfg.num_agents, jnp)

    def advance(s, bid, ask, last, pmid):
        state = MarketState(bid=bid, ask=ask, last_price=last, prev_mid=pmid)
        eb = jnp.where(s == jnp.int32(0), ext_b, zeros_ext)
        ea = jnp.where(s == jnp.int32(0), ext_a, zeros_ext)
        new_state, out = simulate_step(
            cfg, state, step0 + s, market_ids, jnp, bin_orders=None,
            scan=KERNEL_SCAN, ext_buy=eb, ext_ask=ea, agent_chunk=agent_chunk,
            params=params, atype=atype, peer_mid=peer_mid,
        )
        # Steps past n_valid are computed but discarded — the carried state
        # only advances while active.
        active = s < n_valid
        bid = jnp.where(active, new_state.bid, bid)
        ask = jnp.where(active, new_state.ask, ask)
        last = jnp.where(active, new_state.last_price, last)
        pmid = jnp.where(active, new_state.prev_mid, pmid)
        return active, bid, ask, last, pmid, out

    if stats_only:
        st0 = stats_mod.MarketStats(
            count=cnt_ref[...], sum_mid=smid_ref[...], sumsq_mid=ssq_ref[...],
            min_mid=mn_ref[...], max_mid=mx_ref[...], sum_volume=svol_ref[...])

        def body(s, carry):
            bid, ask, last, pmid, st = carry
            active, bid, ask, last, pmid, out = advance(s, bid, ask, last, pmid)
            st = stats_mod.accumulate(st, out.mid, out.volume, active, jnp)
            return bid, ask, last, pmid, st

        bid, ask, last, pmid, st = jax.lax.fori_loop(
            0, chunk, body, (bid, ask, last, pmid, st0))
        out_cnt_ref[...] = st.count
        out_smid_ref[...] = st.sum_mid
        out_ssq_ref[...] = st.sumsq_mid
        out_mn_ref[...] = st.min_mid
        out_mx_ref[...] = st.max_mid
        out_svol_ref[...] = st.sum_volume
    else:
        def body(s, carry):
            bid, ask, last, pmid, pp, vp, mp = carry
            _, bid, ask, last, pmid, out = advance(s, bid, ask, last, pmid)
            # Caller slices the paths to the first n_valid columns.
            pp = write_column(pp, s, out.price)
            vp = write_column(vp, s, out.volume)
            mp = write_column(mp, s, out.mid)
            return bid, ask, last, pmid, pp, vp, mp

        pp0 = jnp.zeros((mb, chunk), jnp.float32)
        vp0 = jnp.zeros((mb, chunk), jnp.float32)
        mp0 = jnp.zeros((mb, chunk), jnp.float32)
        bid, ask, last, pmid, pp, vp, mp = jax.lax.fori_loop(
            0, chunk, body, (bid, ask, last, pmid, pp0, vp0, mp0)
        )
        price_path_ref[...] = pp
        volume_path_ref[...] = vp
        mid_path_ref[...] = mp

    out_bid_ref[...] = bid
    out_ask_ref[...] = ask
    out_last_ref[...] = last
    out_pmid_ref[...] = pmid


def kinetic_clearing_chunk(
    bid: jax.Array, ask: jax.Array, last: jax.Array, pmid: jax.Array,
    step0: jax.Array, n_valid: jax.Array,
    ext_buy: jax.Array, ext_ask: jax.Array,
    *, cfg, chunk: int, mb: int = 8,
    interpret: bool = False, market_ids: Optional[jax.Array] = None,
    agent_chunk: Optional[int] = None,
    params: Optional[MarketParams] = None,
    peer_mid: Optional[jax.Array] = None,
    stats: Optional[stats_mod.MarketStats] = None, stats_only: bool = False,
) -> Tuple[jax.Array, ...]:
    """``num_steps``-parametrized persistent entry for the Session API.

    One trace (per static ``chunk`` length) serves every chunk of up to
    ``chunk`` steps: ``step0``/``n_valid`` are int32[1, 1] runtime scalars,
    and every scenario-varying parameter is a per-market ``[M, 1]`` operand
    (``params``, a :class:`repro.core.params.MarketParams`; defaults to the
    spec's own params, or to a broadcast of a legacy scalar config — the
    scalar default is value-identical to the pre-ensemble constants).
    Deliberately *not* jitted here — the session runner owns the ``jax.jit``
    wrapper so it can donate the state buffers and count traces.

    The market axis is padded to a multiple of ``mb`` with benign zero rows
    (and sliced back), so any M — prime, odd, tiny — runs full sublane-
    aligned tiles; parameter columns pad with zero rows too (a zero-count,
    shock-at-0-with-zero-intensity market whose outputs are discarded).
    ``market_ids`` (optional int32[M] / [M, 1]) carries each row's global
    coordinate for sharded callers; it defaults to ``arange(M)``.

    ``peer_mid`` (optional f32[M, 1]) is the chunk-frozen coupling column
    for arbitrageur agents. When ``None`` it is gathered here from the
    entry ``pmid`` at ``params.coupling_peer`` (self when < 0) over
    *local* row indices — correct whenever all rows are on one device.
    Sharded callers must pass the column explicitly (see the ring halo
    exchange in :mod:`repro.kernels.ops`), since a cross-shard peer is not
    addressable by a local gather.

    Returns ``(bid, ask, last, pmid, price_path[M, chunk],
    volume_path[M, chunk], mid_path[M, chunk])``, or with
    ``stats_only=True`` (which requires the carried ``stats`` accumulators)
    ``(bid, ask, last, pmid, MarketStats)`` — no per-step outputs ever
    reach HBM in that mode; only the first ``n_valid`` path columns are
    meaningful otherwise.
    """
    M, L = bid.shape
    m_padded = pad_to_multiple(M, mb)
    grid = (m_padded // mb,)

    if market_ids is None:
        market_ids = jnp.arange(M, dtype=jnp.int32)
    mids = jnp.reshape(jnp.asarray(market_ids, dtype=jnp.int32), (M, 1))
    if m_padded != M:
        pad_ids = jnp.arange(M, m_padded, dtype=jnp.int32)[:, None]
        mids = jnp.concatenate([mids, pad_ids], axis=0)
    params = resolve_params(cfg, M, params, jnp)
    if peer_mid is None:
        # Single-device default: gather the chunk-entry mids at the peer
        # rows (local indices == global ids here).
        peer_mid = resolve_peer_mids(pmid, params.coupling_peer, jnp)
    bid, ask, last, pmid, ext_buy, ext_ask, peer_mid = (
        _pad_rows(x, m_padded) for x in (bid, ask, last, pmid, ext_buy,
                                         ext_ask, peer_mid))
    params = pad_params(params, m_padded)

    book_spec = pl.BlockSpec((mb, L), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((mb, 1), lambda i: (i, 0))
    step_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    path_spec = pl.BlockSpec((mb, chunk), lambda i: (i, 0))

    state_shapes = (
        jax.ShapeDtypeStruct((m_padded, L), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, L), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, 1), jnp.float32),
        jax.ShapeDtypeStruct((m_padded, 1), jnp.float32),
    )
    in_specs = [step_spec, step_spec, scalar_spec, book_spec, book_spec,
                scalar_spec, scalar_spec, book_spec, book_spec,
                scalar_spec] + [scalar_spec] * NUM_PARAM_OPERANDS
    operands = [step0, n_valid, mids, bid, ask, last, pmid, ext_buy,
                ext_ask, peer_mid] + list(params)

    if stats_only:
        if stats is None:
            raise ValueError("stats_only=True requires the carried `stats` "
                             "accumulators (see repro.core.stats.init_stats)")
        stats = stats_mod.MarketStats(
            *(_pad_rows(jnp.asarray(x, dtype=jnp.float32), m_padded)
              for x in stats))
        stats_shape = jax.ShapeDtypeStruct((m_padded, 1), jnp.float32)
        in_specs += [scalar_spec] * 6
        operands += list(stats)
        out_specs = ((book_spec, book_spec, scalar_spec, scalar_spec)
                     + (scalar_spec,) * 6)
        out_shapes = state_shapes + (stats_shape,) * 6
    else:
        out_specs = (book_spec, book_spec, scalar_spec, scalar_spec,
                     path_spec, path_spec, path_spec)
        out_shapes = state_shapes + (
            jax.ShapeDtypeStruct((m_padded, chunk), jnp.float32),) * 3

    out = pallas_call(
        functools.partial(_chunk_kernel_body, cfg=cfg, mb=mb, chunk=chunk,
                          agent_chunk=agent_chunk, stats_only=stats_only),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*operands)

    out = tuple(x[:M] for x in out)
    if stats_only:
        return out[:4] + (stats_mod.MarketStats(*out[4:]),)
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "mb", "interpret"))
def kinetic_clearing(
    bid: jax.Array, ask: jax.Array, last: jax.Array, pmid: jax.Array,
    *, cfg: MarketConfig, mb: int = 8, interpret: bool = False,
) -> Tuple[jax.Array, ...]:
    """Run the full S-step ensemble simulation in one persistent kernel.

    Args:
      bid/ask: float32[M, L] opening books; last/pmid: float32[M, 1].
    Returns:
      (bid, ask, last, pmid, price_path[M, S], volume_path[M, S]).
    """
    M, L = bid.shape
    S = cfg.num_steps
    if M % mb:
        raise ValueError(f"M={M} not divisible by tile mb={mb}")
    grid = (M // mb,)

    book_spec = pl.BlockSpec((mb, L), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((mb, 1), lambda i: (i, 0))
    path_spec = pl.BlockSpec((mb, S), lambda i: (i, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((M, L), jnp.float32),
        jax.ShapeDtypeStruct((M, L), jnp.float32),
        jax.ShapeDtypeStruct((M, 1), jnp.float32),
        jax.ShapeDtypeStruct((M, 1), jnp.float32),
        jax.ShapeDtypeStruct((M, S), jnp.float32),
        jax.ShapeDtypeStruct((M, S), jnp.float32),
    )
    return pallas_call(
        functools.partial(_kernel_body, cfg=cfg, mb=mb),
        grid=grid,
        in_specs=[book_spec, book_spec, scalar_spec, scalar_spec],
        out_specs=(book_spec, book_spec, scalar_spec, scalar_spec,
                   path_spec, path_spec),
        out_shape=out_shapes,
        interpret=interpret,
    )(bid, ask, last, pmid)
