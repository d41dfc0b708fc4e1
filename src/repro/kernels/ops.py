"""Session runners for the Pallas engines.

On a TPU backend the kernels lower through Mosaic; on any other backend
(the CPU test runs) they execute in Pallas interpret mode, where the kernel
body runs as plain JAX ops with the same bits. The runner decides from
``jax.default_backend()``; there is no option to force either mode.

Both Pallas families are plumbed through the Session API: the chunk entries
(:func:`repro.kernels.kinetic_clearing.kinetic_clearing_chunk`,
:func:`repro.kernels.naive_clearing.naive_clearing_chunk`) take runtime
``(step0, n_valid)`` scalars plus the per-market
:class:`repro.core.params.MarketParams` operands over a static chunk
length, so one trace serves any requested step count *and any scenario
mixture*; the runner jits them with donated state buffers (params are
never donated — a session's scenario operands persist device-resident).
``engine.simulate`` reaches them through the same session factories.

Scaling knobs (Engine backend_opts, all composable):

  * ``devices=N`` / ``mesh=`` — shard the market axis across a 1-D
    ``("markets",)`` device mesh with ``shard_map`` over the chunk kernel.
    Each shard receives its rows' true *global* market ids — and its rows
    of every parameter column — so a sharded heterogeneous ensemble is
    bitwise-identical to the single-device run; state stays
    device-resident and donated, sharded row-wise (uneven M is padded to a
    whole tile per shard and sliced back).
  * ``stats_only=True`` — replace the per-step path outputs with in-kernel
    running statistics (see :mod:`repro.core.stats`): the kernel's HBM
    output traffic drops from Θ(M·chunk) to Θ(M), independent of horizon.
  * ``mb=`` / ``agent_chunk=`` / ``autotune=`` — tile selection. By
    default the market axis is padded to sublane-aligned MB=8 tiles
    (:func:`repro.kernels.autotune.auto_tile`), with the widest level tile
    that fits the VMEM budget; ``autotune=True`` (or ``"auto"``, which
    sweeps only when lowering via Mosaic on a TPU) times (MB, agent-chunk)
    candidates, each at its widest fitting level tile, on first compile
    and caches the winner per ``(device-kind, L, A, chunk)`` for every
    engine in the process.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import session
from repro.core import stats as stats_mod
from repro.core.params import EnsembleSpec, MarketParams
from repro.core.step import MarketState, StepOutput, initial_state
from repro.kernels import autotune as tune
from repro.kernels.kinetic_clearing import (_pad_rows, kinetic_clearing_chunk,
                                            pad_params)
from repro.kernels.naive_clearing import naive_clearing_chunk
from repro.launch.mesh import make_markets_mesh
from repro.launch.sharding import market_sharding, replicated_sharding
from repro.ops.metrics import span


def _resolve_mesh(mesh, devices):
    if mesh is not None:
        return mesh
    if devices is not None:
        return make_markets_mesh(devices)
    return None


def _zero_params(num_markets: int) -> MarketParams:
    """Valid all-zero parameter columns (autotune timing operands)."""
    return MarketParams.zeros(num_markets, jnp)


class PallasChunkRunner(session.ChunkRunner):
    """jit wrapper around a chunk-parametrized Pallas entry point.

    Optionally shards the market axis over a ``("markets",)`` mesh and/or
    runs in ``stats_only`` mode; see the module docstring for the knobs.
    """

    xp = jnp
    compiled = True
    env_traceable = True
    env_runtime_seed = False  # the kernel trace bakes the RNG seed

    def __init__(self, kernel_chunk_fn, spec: EnsembleSpec, chunk: int,
                 mb: Optional[int] = None, stats_only: bool = False,
                 agent_chunk: Optional[int] = None,
                 devices: Optional[int] = None, mesh=None,
                 autotune="auto"):
        super().__init__()
        self.spec = spec
        self.chunk = int(chunk)
        self.stats_only = bool(stats_only)
        #: Pallas interpret mode: only away from a TPU (see module doc).
        self.interpret = jax.default_backend() != "tpu"
        self._kernel_chunk_fn = kernel_chunk_fn
        self._mesh = _resolve_mesh(mesh, devices)
        M, L = spec.num_markets, spec.num_levels

        # Per-shard market count: tiles are chosen for (and padding applied
        # to) each shard's local slice.
        n_shards = self._mesh.devices.size if self._mesh is not None else 1
        self._n_shards = n_shards
        m_local = -(-M // n_shards)
        self.tile = self._resolve_tile(kernel_chunk_fn, spec, m_local, mb,
                                       agent_chunk, autotune)

        self._zero_ext = (jnp.zeros((M, L), jnp.float32),
                          jnp.zeros((M, L), jnp.float32))

        pure_chunk = self._build_chunk_fn(self.chunk, self.stats_only)

        def chunk_fn(state, stats, params, step0, n_valid,
                     ext_buy, ext_ask):
            self._trace_count += 1  # python side effect: trace-time only
            return pure_chunk(state, stats, params, step0, n_valid,
                              ext_buy, ext_ask)

        if self._mesh is None:
            self._chunk_fn = jax.jit(chunk_fn, donate_argnums=(0, 1))
        else:
            row = self._row_sharding = market_sharding(self._mesh)
            rep = replicated_sharding(self._mesh)
            state_sh = MarketState(row, row, row, row)
            params_sh = MarketParams(*(row,) * len(MarketParams._fields))
            stats_sh = (stats_mod.MarketStats(*(row,) * 6)
                        if self.stats_only else None)
            out_sh = ((state_sh, stats_sh) if self.stats_only
                      else (state_sh, (row, row, row)))
            self._chunk_fn = jax.jit(
                chunk_fn, donate_argnums=(0, 1),
                in_shardings=(state_sh, stats_sh, params_sh, rep, rep,
                              row, row),
                out_shardings=out_sh)

    def _build_chunk_fn(self, chunk: int, stats_only: bool):
        """Pure ``(state, stats, params, step0, n_valid, ext_buy, ext_ask)
        -> (MarketState, payload)`` chunk executor around the kernel entry.

        The single construction site for both front doors: the Session
        wraps the runner-chunk instance in ``jax.jit`` with donated state
        buffers; the RL env (:meth:`env_step_fn`) embeds a ``chunk=1``
        instance inside its own jitted step/rollout graphs. Mesh-opened
        runners wrap the kernel in the same ``shard_map`` either way, so
        env rollouts compose with market-axis sharding unchanged.
        """
        spec = self.spec
        kernel_chunk_fn = self._kernel_chunk_fn
        M = spec.num_markets
        kernel_kwargs = dict(cfg=spec, chunk=chunk, mb=self.tile.mb,
                             interpret=self.interpret,
                             agent_chunk=self.tile.agent_chunk,
                             level_tile=self.tile.level_tile,
                             stats_only=stats_only)

        if self._mesh is None:
            def pure_chunk(state, stats, params, step0, n_valid,
                           ext_buy, ext_ask):
                return self._split(kernel_chunk_fn(
                    state.bid, state.ask, state.last_price, state.prev_mid,
                    step0, n_valid, ext_buy, ext_ask, params=params,
                    stats=stats, **kernel_kwargs), stats_only)

            return pure_chunk

        mesh_ = self._mesh
        n_shards = self._n_shards
        m_shard = tune.pad_to_multiple(-(-M // self._n_shards), self.tile.mb)
        m_padded = self._n_shards * m_shard
        ring = [(i, (i + 1) % n_shards) for i in range(n_shards)]

        def shard_body(step0, n_valid, mids, bid, ask, last, pmid,
                       ext_buy, ext_ask, params, stats):
            # Coupling halo exchange: a cross-market peer may live on
            # another shard, so the chunk-entry mids circulate the ring
            # once (`ppermute` all-gather) before the local gather. The
            # global padding sits at the END of the market axis, so a
            # real row's global id IS its row index in `full` — the
            # gathered column is bitwise what the single-device entry
            # computes, which is what makes coupled sharded runs
            # bitwise-identical (and `peer < 0` resolves to the row's own
            # global id, i.e. self-coupling).
            idx = jax.lax.axis_index("markets")
            full = jnp.zeros((m_padded, 1), pmid.dtype)
            cur = pmid
            for k in range(n_shards):
                src = (idx - k) % n_shards
                full = jax.lax.dynamic_update_slice(full, cur,
                                                    (src * m_shard, 0))
                if k + 1 < n_shards:
                    cur = jax.lax.ppermute(cur, "markets", ring)
            peer = jnp.reshape(
                jnp.asarray(params.coupling_peer, jnp.int32), (-1, 1))
            resolved = jnp.where(peer < jnp.int32(0), mids, peer)
            peer_mid = jnp.take_along_axis(full, resolved, axis=0)
            return kernel_chunk_fn(
                bid, ask, last, pmid, step0, n_valid, ext_buy, ext_ask,
                market_ids=mids, params=params, peer_mid=peer_mid,
                stats=stats, **kernel_kwargs)

        row_params = MarketParams(*(P("markets", None),)
                                  * len(MarketParams._fields))
        sharded_call = jax.shard_map(
            shard_body, mesh=mesh_,
            in_specs=(P(), P(), P("markets", None), P("markets", None),
                      P("markets", None), P("markets", None),
                      P("markets", None), P("markets", None),
                      P("markets", None), row_params,
                      P("markets", None) if stats_only else None),
            out_specs=P("markets", None), check_vma=False)

        def pure_chunk(state, stats, params, step0, n_valid,
                       ext_buy, ext_ask):
            # Pad/slice every call rather than carrying padded state:
            # Θ(M·L) per chunk vs the kernel's Θ(chunk·A·L) work, and it
            # keeps session state — and therefore snapshots — in the
            # canonical [M, ...] layout on every device topology.
            padded = [_pad_rows(x, m_padded) for x in state]
            eb = _pad_rows(ext_buy, m_padded)
            ea = _pad_rows(ext_ask, m_padded)
            pp = pad_params(params, m_padded)
            # Global row coordinates: rows < M are real markets, pad rows
            # get distinct ids >= M whose streams are discarded.
            mids = jnp.arange(m_padded, dtype=jnp.int32)[:, None]
            st = None
            if stats_only:
                st = stats_mod.MarketStats(
                    *(_pad_rows(x, m_padded) for x in stats))
            out = sharded_call(step0, n_valid, mids, *padded, eb, ea,
                               pp, st)
            return self._split(
                tuple(x[:M] for x in jax.tree_util.tree_leaves(out)),
                stats_only)

        return pure_chunk

    def env_step_fn(self):
        """Traceable per-step core for :class:`repro.env.MarketEnv`: one
        ``chunk=1`` persistent-kernel call (sharded when the runner is),
        embeddable in the env's jitted ``lax.scan`` rollouts."""
        pure_step = self._build_chunk_fn(1, False)
        one = jnp.ones((1, 1), jnp.int32)

        def step_core(market, params, t, ext_buy, ext_ask, seed, aux):
            step0 = jnp.reshape(jnp.asarray(t, dtype=jnp.int32), (1, 1))
            state, payload = pure_step(market, None, params, step0, one,
                                       ext_buy, ext_ask)
            pp, vp, mp = payload
            return state, StepOutput(price=pp, volume=vp, mid=mp), aux

        return step_core

    # ---- tile selection ----
    def _resolve_tile(self, kernel_chunk_fn, spec, m_local, mb, agent_chunk,
                      autotune) -> tune.TileChoice:
        L, A = spec.num_levels, spec.num_agents

        def fitted(tile):
            return tile._replace(level_tile=tune.fitting_level_tile(
                tile, L, A, self.chunk))

        if mb is not None:
            return fitted(tune.TileChoice(
                mb=mb, m_padded=tune.pad_to_multiple(m_local, mb),
                agent_chunk=(agent_chunk if agent_chunk is not None
                             else tune.default_agent_chunk(A))))
        sweep = autotune is True or (autotune == "auto"
                                     and not self.interpret)
        heuristic = tune.auto_tile(m_local, A)
        if agent_chunk is not None:
            heuristic = heuristic._replace(agent_chunk=agent_chunk)
        heuristic = fitted(heuristic)
        if not sweep:
            return heuristic

        def time_candidate(choice: tune.TileChoice) -> float:
            M, L = m_local, spec.num_levels
            m0 = jnp.float32(spec.mid0)
            bid = jnp.zeros((M, L), jnp.float32)
            scalars = jnp.ones((M, 1), jnp.float32) * m0
            step0 = jnp.zeros((1, 1), jnp.int32)
            nv = jnp.full((1, 1), self.chunk, jnp.int32)
            zp = _zero_params(M)
            st = (stats_mod.init_stats(M, jnp) if self.stats_only else None)

            @jax.jit
            def fn():
                return kernel_chunk_fn(
                    bid, bid, scalars, scalars, step0, nv, bid, bid,
                    cfg=spec, chunk=self.chunk, mb=choice.mb,
                    interpret=self.interpret, agent_chunk=choice.agent_chunk,
                    level_tile=choice.level_tile,
                    params=zp, stats=st, stats_only=self.stats_only)

            return tune.time_call(fn, jax.block_until_ready)

        # An explicitly pinned agent_chunk is never swept away, and distinct
        # kernel configurations (family / stats mode) never share a measured
        # winner.
        key = tune.tune_key(
            L, A, self.chunk, kernel=kernel_chunk_fn.__name__,
            stats_only=self.stats_only, agent_chunk=agent_chunk)
        pin = agent_chunk if agent_chunk is not None else ...
        cands = tune.candidate_tiles(m_local, A, agent_chunk=pin,
                                     num_levels=L, chunk=self.chunk)
        pruned = len(tune.candidate_tiles(m_local, A, agent_chunk=pin))
        return tune.autotune_tile(key, time_candidate, cands,
                                  fallback=heuristic, num_markets=m_local,
                                  pruned=pruned - len(cands))

    # ---- placement hooks (sharded state stays sharded across snapshots) ----
    def init_state(self, spec: EnsembleSpec) -> MarketState:
        return self.to_device(initial_state(spec, np))

    def to_device(self, state: MarketState) -> MarketState:
        state = super().to_device(state)
        if self._mesh is None:
            return state
        return MarketState(*(jax.device_put(x, self._row_sharding)
                             for x in state))

    def params_to_device(self, params: MarketParams) -> MarketParams:
        params = super().params_to_device(params)
        if self._mesh is None:
            return params
        return MarketParams(*(jax.device_put(x, self._row_sharding)
                              for x in params))

    def init_stats(self, spec: EnsembleSpec):
        stats = super().init_stats(spec)
        if stats is None or self._mesh is None:
            return stats
        return self.stats_to_device(stats)

    def stats_to_device(self, stats):
        stats = super().stats_to_device(stats)
        if self._mesh is None:
            return stats
        return stats_mod.MarketStats(
            *(jax.device_put(x, self._row_sharding) for x in stats))

    # ---- execution ----
    def _split(self, out, stats_only: Optional[bool] = None):
        """Kernel output tuple -> (MarketState, payload)."""
        if stats_only is None:
            stats_only = self.stats_only
        state = MarketState(bid=out[0], ask=out[1], last_price=out[2],
                            prev_mid=out[3])
        if stats_only:
            rest = out[4]
            if not isinstance(rest, stats_mod.MarketStats):
                rest = stats_mod.MarketStats(*out[4:])
            return state, rest
        return state, tuple(out[4:])

    def run(self, state: MarketState, params: MarketParams, aux,
            step0: int, n: int, ext,
            stats=None) -> Tuple[MarketState, Any, session.StepBatch, Any]:
        eb, ea = self._zero_ext if ext is None else ext
        host_bytes = 8 + (0 if ext is None else eb.nbytes + ea.nbytes)
        with span("kinetic.dispatch.operands", bytes=host_bytes):
            step0_arr = jnp.full((1, 1), step0, dtype=jnp.int32)
            nvalid_arr = jnp.full((1, 1), n, dtype=jnp.int32)
            eb, ea = jnp.asarray(eb), jnp.asarray(ea)
        with span("kinetic.dispatch.launch"):
            new_state, payload = self._chunk_fn(
                state, stats if self.stats_only else None, params,
                step0_arr, nvalid_arr, eb, ea)
        if self.stats_only:
            empty = jnp.zeros((self.spec.num_markets, 0), jnp.float32)
            return (new_state, aux,
                    session.StepBatch(price=empty, volume=empty, mid=empty),
                    payload)
        with span("kinetic.dispatch.slice"):
            pp, vp, mp = payload
            batch = session.StepBatch(
                price=pp[:, :n], volume=vp[:, :n], mid=mp[:, :n])
        return new_state, aux, batch, None


@session.register_backend("pallas-kinetic")
def open_kinetic_runner(spec, chunk: int, **opts: Any) -> PallasChunkRunner:
    """The paper's engine: persistent, VMEM-resident, one launch per chunk."""
    return PallasChunkRunner(kinetic_clearing_chunk, EnsembleSpec.coerce(spec),
                             chunk, **opts)


@session.register_backend("pallas-naive")
def open_naive_runner(spec, chunk: int, **opts: Any) -> PallasChunkRunner:
    """Ablation: per-step kernel launches, HBM-resident book."""
    return PallasChunkRunner(naive_clearing_chunk, EnsembleSpec.coerce(spec),
                             chunk, **opts)
