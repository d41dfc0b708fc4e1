"""Order binning: one-hot MXU contraction vs scatter reference (bitwise),
plus tile-selection edge cases (legacy ``pick_tile`` divisors and the
padded ``auto_tile`` policy that replaced them for the session entries).

The one-hot contraction is the TPU-native replacement for the paper's
shared-memory atomicAdd histogram; because quantities are exact small
integers in f32, the two binnings must agree *exactly* (==, not allclose) —
the foundation of the cross-engine bitwise-identity claim.
"""
import numpy as np
import pytest

from repro.core.step import bin_orders_onehot
from repro.kernels.autotune import (auto_tile, candidate_tiles,
                                    default_agent_chunk, pad_to_multiple)
from repro.kernels.kinetic_clearing import pick_tile


def _bin_orders_scatter_ref(side_buy, price, qty, M, L):
    """Scalar-loop scatter reference (the paper's atomicAdd semantics)."""
    buy = np.zeros((M, L), dtype=np.float32)
    sell = np.zeros((M, L), dtype=np.float32)
    for m in range(M):
        for a in range(price.shape[1]):
            tgt = buy if side_buy[m, a] else sell
            tgt[m, price[m, a]] += qty[m, a]
    return buy, sell


def _random_orders(rng, M, A, L, q_max=8):
    side_buy = rng.random((M, A)) < 0.5
    price = rng.integers(0, L, size=(M, A)).astype(np.int32)
    qty = (1.0 + rng.integers(0, q_max, size=(M, A))).astype(np.float32)
    return side_buy, price, qty


@pytest.mark.parametrize("M,A,L", [
    (1, 1, 4),
    (4, 16, 16),
    (8, 64, 32),
    (3, 200, 128),   # A >> L: heavy per-level accumulation
    (16, 7, 64),     # A < L: sparse histogram
])
def test_onehot_matches_scatter_exactly(M, A, L):
    rng = np.random.default_rng(M * 100 + A)
    side_buy, price, qty = _random_orders(rng, M, A, L)
    want_buy, want_sell = _bin_orders_scatter_ref(side_buy, price, qty, M, L)
    got_buy, got_sell = bin_orders_onehot(side_buy, price, qty, L, np)
    # exact-integer f32 equality, not allclose
    assert got_buy.dtype == np.float32 and got_sell.dtype == np.float32
    assert (got_buy == want_buy).all()
    assert (got_sell == want_sell).all()


def _edge_orders(case, rng):
    """Orders at the edges of the binning's domain, L=32: (orders, chunk)."""
    side_buy, price, qty = _random_orders(rng, 4, 48, 32)
    if case == "one_sided":          # markets 0, 2 all buy; 1, 3 all sell
        side_buy = np.repeat((np.arange(4) % 2 == 0)[:, None], 48, axis=1)
    elif case == "edge_ticks":       # only ticks 0 and L - 1
        price = np.where(rng.random((4, 48)) < 0.5, 0, 31).astype(np.int32)
    elif case == "whale":            # whale-size quantities among small ones
        qty = np.where(rng.random((4, 48)) < 0.5, 32.0, qty).astype(np.float32)
    elif case == "ragged_chunk":     # A=200 is not a multiple of 64
        return _random_orders(rng, 4, 200, 32), 64
    return (side_buy, price, qty), 16


def _assert_matches_scatter(orders, L, xp, agent_chunk):
    side_buy, price, qty = orders
    want = _bin_orders_scatter_ref(side_buy, price, qty, price.shape[0], L)
    got = bin_orders_onehot(xp.asarray(side_buy), xp.asarray(price),
                            xp.asarray(qty), L, xp, agent_chunk=agent_chunk)
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == np.float32 and g.shape == w.shape
        assert (g == w).all()


def _xp(name):
    import jax.numpy as jnp

    return {"numpy": np, "jnp": jnp}[name]


@pytest.mark.parametrize("xp", ["numpy", "jnp"])
@pytest.mark.parametrize("case", ["one_sided", "edge_ticks", "whale",
                                  "ragged_chunk"])
def test_onehot_edge_orders_match_scatter(case, xp):
    rng = np.random.default_rng(23)
    orders, agent_chunk = _edge_orders(case, rng)
    _assert_matches_scatter(orders, 32, _xp(xp), agent_chunk)


@pytest.mark.parametrize("xp", ["numpy", "jnp"])
@pytest.mark.parametrize("agent_chunk", [64, 128, None])
@pytest.mark.parametrize("A", [256, 1024])
def test_onehot_cell_shapes_match_scatter(A, agent_chunk, xp):
    """The benchmark cells' widths (L=128; A=256 and A=1024), cut to a few
    markets, at every agent chunk the tile sweep tries."""
    rng = np.random.default_rng(A + (agent_chunk or 0))
    orders = _random_orders(rng, 3, A, 128)
    _assert_matches_scatter(orders, 128, _xp(xp), agent_chunk)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def test_onehot_is_one_level_major_contraction_per_agent_chunk():
    """Buy and sell share one MXU pass per agent chunk, over a level-major
    [M, L, Ac] one-hot selected in f32: the two-pass, agent-major form
    (two matvecs re-latching one [Ac, L] one-hot) must not come back."""
    import jax
    import jax.numpy as jnp

    M, A, L, ac = 3, 256, 128, 128
    closed = jax.make_jaxpr(
        lambda s, p, q: bin_orders_onehot(s, p, q, L, jnp, agent_chunk=ac))(
        jnp.zeros((M, A), bool), jnp.zeros((M, A), jnp.int32),
        jnp.zeros((M, A), jnp.float32))
    eqns = list(_eqns(closed.jaxpr))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == A // ac
    for dot in dots:
        lhs, rhs = (v.aval for v in dot.invars)
        assert lhs.shape == (M, 2, ac) and lhs.dtype == jnp.float32
        assert rhs.shape == (M, L, ac) and rhs.dtype == jnp.float32
        # contract the agent axis, last in both; batch over markets
        assert dot.params["dimension_numbers"] == (((2,), (2,)), ((0,), (0,)))
    # the one-hot is selected in f32, never converted from its mask
    assert not any(e.primitive.name == "convert_element_type"
                   and e.outvars[0].aval.ndim == 3 for e in eqns)


def test_onehot_matches_scatter_jax():
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    side_buy, price, qty = _random_orders(rng, 6, 48, 32)
    want_buy, want_sell = _bin_orders_scatter_ref(side_buy, price, qty, 6, 32)
    got_buy, got_sell = bin_orders_onehot(
        jnp.asarray(side_buy), jnp.asarray(price), jnp.asarray(qty), 32, jnp)
    assert (np.asarray(got_buy) == want_buy).all()
    assert (np.asarray(got_sell) == want_sell).all()


def test_onehot_mass_conservation():
    rng = np.random.default_rng(11)
    side_buy, price, qty = _random_orders(rng, 4, 32, 16)
    buy, sell = bin_orders_onehot(side_buy, price, qty, 16, np)
    assert buy.sum() + sell.sum() == qty.sum()
    assert (buy.sum(axis=1) + sell.sum(axis=1) == qty.sum(axis=1)).all()


class TestPickTile:
    def test_divisor_and_bound(self):
        for m in range(1, 300):
            mb = pick_tile(m)
            assert 1 <= mb <= min(8, m)
            assert m % mb == 0

    def test_prime_m_degenerates_to_one(self):
        # A prime M > target has no divisor <= target except 1.
        for m in (11, 13, 8191):
            assert pick_tile(m) == 1

    def test_m_smaller_than_target(self):
        # M <= target: the whole ensemble is one tile.
        for m in (1, 2, 3, 5, 7, 8):
            assert pick_tile(m) == m
        assert pick_tile(3, target=8) == 3

    def test_custom_target(self):
        assert pick_tile(64, target=16) == 16
        assert pick_tile(24, target=16) == 12
        assert pick_tile(17, target=16) == 1


class TestAutoTile:
    """The padded tile policy: prime/odd M must never degrade to MB=1."""

    def test_prime_matches_even_tile_shape(self):
        # The seed's pick_tile pathology: M=63 ran MB=1. The padded policy
        # must give M=63 the exact tile shape (and grid) of M=64.
        assert auto_tile(63) == auto_tile(64)
        assert auto_tile(63).mb == 8
        assert auto_tile(63).m_padded == 64
        assert auto_tile(63).grid == 8

    def test_never_degrades(self):
        for m in (1, 3, 7, 11, 13, 63, 97, 8191):
            choice = auto_tile(m)
            assert choice.mb == 8, m
            assert choice.m_padded % choice.mb == 0, m
            assert choice.m_padded >= m, m
            assert choice.m_padded - m < choice.mb, m

    def test_agent_chunk_heuristic(self):
        assert default_agent_chunk(64) is None
        assert default_agent_chunk(128) is None
        assert default_agent_chunk(256) == 128
        assert auto_tile(16, num_agents=256).agent_chunk == 128

    def test_pad_to_multiple(self):
        assert pad_to_multiple(63, 8) == 64
        assert pad_to_multiple(64, 8) == 64
        assert pad_to_multiple(1, 8) == 8

    def test_candidates_cover_sublane_tiles(self):
        cands = candidate_tiles(63, 256)
        assert len(cands) == len(set(cands))
        assert all(c.mb % 8 == 0 for c in cands)
        assert all(c.m_padded % c.mb == 0 for c in cands)
        assert {c.mb for c in cands} == {8, 16}

    def test_candidates_honor_pinned_agent_chunk(self):
        # An explicit agent_chunk (a caller's VMEM bound) is never swept.
        assert all(c.agent_chunk == 32
                   for c in candidate_tiles(63, 256, agent_chunk=32))
        assert all(c.agent_chunk is None
                   for c in candidate_tiles(63, 256, agent_chunk=None))

    def test_sweep_winner_repadded_per_ensemble_size(self):
        from repro.kernels import autotune as tune

        tune.clear_tune_cache()
        try:
            key = tune.tune_key(32, 16, 4, kernel="k")
            fb = auto_tile(63, 16)
            first = tune.autotune_tile(key, lambda c: 1.0,
                                       candidate_tiles(63, 16),
                                       fallback=fb, num_markets=63)
            # cache hit for a different M reuses (mb, agent_chunk) but must
            # re-derive m_padded for the caller's ensemble size
            again = tune.autotune_tile(key, lambda c: 1.0, [],
                                       fallback=fb, num_markets=200)
            assert again.mb == first.mb
            assert again.m_padded == pad_to_multiple(200, first.mb)
        finally:
            tune.clear_tune_cache()

    def test_sweep_all_failed_falls_back_to_heuristic(self):
        from repro.kernels import autotune as tune

        tune.clear_tune_cache()
        try:
            def boom(choice):
                raise RuntimeError("RESOURCE_EXHAUSTED: tile exceeds VMEM")

            fb = auto_tile(63, 256)  # keeps the A-derived agent_chunk
            got = tune.autotune_tile(tune.tune_key(32, 256, 4, kernel="k"),
                                     boom, candidate_tiles(63, 256),
                                     fallback=fb, num_markets=63)
            assert got == fb
        finally:
            tune.clear_tune_cache()

    def test_sweep_reraises_non_oom_failure(self):
        """A compile rejection is a bug to surface, not a tile to skip."""
        from repro.kernels import autotune as tune

        tune.clear_tune_cache()
        try:
            def rejected(choice):
                raise RuntimeError("Mosaic failed to compile TPU kernel: "
                                   "unsupported op")

            with pytest.raises(RuntimeError, match="Mosaic"):
                tune.autotune_tile(tune.tune_key(32, 256, 4, kernel="k"),
                                   rejected, candidate_tiles(63, 256),
                                   fallback=auto_tile(63, 256),
                                   num_markets=63)
            assert tune.last_sweep_report() is None
        finally:
            tune.clear_tune_cache()

    @pytest.mark.parametrize("text,oom", [
        ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem", True),
        ("XlaRuntimeError: out of memory", True),
        ("device OOM while allocating", True),
        ("Mosaic failed to compile TPU kernel: unsupported cast", False),
        ("not enough room in the grid", False),
    ])
    def test_is_oom_error(self, text, oom):
        from repro.kernels import autotune as tune

        assert tune.is_oom_error(RuntimeError(text)) is oom


@pytest.mark.parametrize("agent_chunk", [1, 3, 16, 200])
def test_onehot_agent_chunking_bitwise(agent_chunk):
    """The VMEM-bounding agent chunking must be bitwise-invisible."""
    rng = np.random.default_rng(17)
    side_buy, price, qty = _random_orders(rng, 5, 48, 32)
    want = bin_orders_onehot(side_buy, price, qty, 32, np)
    got = bin_orders_onehot(side_buy, price, qty, 32, np,
                            agent_chunk=agent_chunk)
    assert (got[0] == want[0]).all()
    assert (got[1] == want[1]).all()


@pytest.mark.parametrize("xp", ["numpy", "jnp"])
@pytest.mark.parametrize("agent_chunk", [None, 128])
@pytest.mark.parametrize("level_tile", [128, 512, 2048, 4096, None])
@pytest.mark.parametrize("case", ["random", "edge_ticks"])
def test_onehot_level_tiling_bitwise_on_4096_levels(case, level_tile,
                                                    agent_chunk, xp):
    """Level-tiled against untiled binning at L=4096 (the widest grid),
    and both against the scatter reference; ``edge_ticks`` puts every
    order on level 0 or L - 1, where marketable orders and whale sweeps
    land."""
    L = 4096
    rng = np.random.default_rng(4096 + (level_tile or 0))
    side_buy, price, qty = _random_orders(rng, 3, 256, L, q_max=32)
    if case == "edge_ticks":
        price = np.where(rng.random(price.shape) < 0.5, 0,
                         L - 1).astype(np.int32)
    x = _xp(xp)
    want = bin_orders_onehot(x.asarray(side_buy), x.asarray(price),
                             x.asarray(qty), L, x)
    got = bin_orders_onehot(x.asarray(side_buy), x.asarray(price),
                            x.asarray(qty), L, x, agent_chunk=agent_chunk,
                            level_tile=level_tile)
    ref = _bin_orders_scatter_ref(side_buy, price, qty, 3, L)
    for g, w, r in zip(got, want, ref):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == np.float32 and g.shape == (3, L)
        assert (g == w).all() and (g == r).all()


def test_untiled_level_tile_traces_the_single_tile_code():
    """``level_tile >= L`` traces exactly the untiled binning."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    side_buy, price, qty = _random_orders(rng, 2, 256, 128)

    def jaxpr(**kw):
        return str(jax.make_jaxpr(lambda s, p, q: bin_orders_onehot(
            s, p, q, 128, jnp, agent_chunk=128, **kw))(side_buy, price, qty))

    assert jaxpr() == jaxpr(level_tile=128) == jaxpr(level_tile=4096)
    assert jaxpr() != jaxpr(level_tile=64)
