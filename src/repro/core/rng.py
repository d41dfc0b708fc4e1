"""Stateless counter-based RNG (paper §III-G), adapted for TPU.

The paper uses SplitMix64 keyed on ``(seed, gid, step, channel)``. TPU vector
units have no 64-bit integer path, so the production generator here is
``kinetic_hash32`` — the same *pattern* (stateless, splittable, pure function
of coordinates) built from chained 32-bit avalanche mixers (lowbias32 /
murmur3-style finalizers). True SplitMix64 is implemented in NumPy uint64 for
the statistical-equivalence reference backend, mirroring the paper's
CPU-reference-with-different-RNG comparison.

All functions are array-module polymorphic: pass ``xp=numpy`` or
``xp=jax.numpy`` (including inside Pallas kernel bodies). Given identical
inputs they produce bitwise-identical uint32 streams in every backend.
"""
from __future__ import annotations

import numpy as np

# uint32 constants (lowbias32 by C. Wellons + murmur3/xxhash primes)
_M1 = 0x7FEB352D
_M2 = 0x846CA68B
_GOLDEN = 0x9E3779B9
_K_GID = 0x85EBCA6B
_K_STEP = 0xC2B2AE35
_K_CHAN = 0x27D4EB2F


def _u32(xp, value):
    if isinstance(value, int):
        # Pre-wrap Python ints: jnp.asarray would reject ints > int32 max.
        value = np.uint32(value & 0xFFFFFFFF)
    return xp.asarray(value).astype(xp.uint32)


def mix32(x, xp):
    """lowbias32 avalanche finalizer over uint32 arrays."""
    c1 = _u32(xp, _M1)
    c2 = _u32(xp, _M2)
    x = x ^ (x >> 16)
    x = x * c1
    x = x ^ (x >> 15)
    x = x * c2
    x = x ^ (x >> 16)
    return x


def kinetic_hash32(seed, gid, step, channel, xp):
    """Pure function of (seed, gid, step, channel) -> uint32.

    Absorbs each key coordinate with a distinct odd multiplier, applying a
    full avalanche between absorptions (two multiply-xorshift rounds each),
    analogous to SplitMix64's stream splitting.
    """
    seed = _u32(xp, seed)
    gid = _u32(xp, gid)
    step = _u32(xp, step)
    channel = _u32(xp, channel)
    x = seed ^ _u32(xp, _GOLDEN)
    x = mix32(x + gid * _u32(xp, _K_GID), xp)
    x = mix32(x + step * _u32(xp, _K_STEP), xp)
    x = mix32(x + channel * _u32(xp, _K_CHAN), xp)
    return x


def uniform32(seed, gid, step, channel, xp):
    """Uniform float32 in [0, 1) with exactly 24 random mantissa bits.

    Using the top 24 bits keeps the uint32->float32 conversion exact and
    guarantees the result is strictly below 1.0 (a raw 32-bit conversion can
    round up to 2**32 and yield exactly 1.0, which would overflow the
    integer-quantity draw q = 1 + floor(u * q_max)).

    The top 24 bits fit in int32, so the conversion goes through int32:
    the same value, and Mosaic has no uint32 -> float32 cast.
    """
    bits = kinetic_hash32(seed, gid, step, channel, xp)
    hi24 = (bits >> 8).astype(xp.int32).astype(xp.float32)
    return hi24 * xp.float32(2.0 ** -24)


# ---------------------------------------------------------------------------
# SplitMix64 (paper Eq. 8-10) — NumPy-only, used by the `numpy-splitmix64`
# reference backend for the statistical-equivalence experiment.
# ---------------------------------------------------------------------------
_SM64_1 = np.uint64(0xBF58476D1CE4E5B9)
_SM64_2 = np.uint64(0x94D049BB133111EB)
_SM64_G = np.uint64(0x9E3779B97F4A7C15)


def splitmix64(coord: np.ndarray) -> np.ndarray:
    """SplitMix64 finalizer of a uint64 counter coordinate (paper Eq. 8-10)."""
    z = np.asarray(coord, dtype=np.uint64)
    with np.errstate(over="ignore"):  # modular uint64 arithmetic by design
        z = (z ^ (z >> np.uint64(30))) * _SM64_1
        z = (z ^ (z >> np.uint64(27))) * _SM64_2
        return z ^ (z >> np.uint64(31))


def splitmix64_coord(seed, gid, step, channel) -> np.ndarray:
    """Counter coordinate hash(gid, step, channel, seed) (paper Eq. 7)."""
    gid = np.asarray(gid, dtype=np.uint64)
    step = np.asarray(step, dtype=np.uint64)
    channel = np.asarray(channel, dtype=np.uint64)
    seed = np.asarray(seed, dtype=np.uint64)
    with np.errstate(over="ignore"):  # modular uint64 arithmetic by design
        coord = seed * _SM64_G + gid
        coord = splitmix64(coord + step * _SM64_1)
        coord = coord + channel * _SM64_2
    return coord


def splitmix64_uniform(seed, gid, step, channel) -> np.ndarray:
    """Uniform float32 in [0,1) from SplitMix64 (top 24 bits)."""
    bits = splitmix64(splitmix64_coord(seed, gid, step, channel))
    hi24 = (bits >> np.uint64(40)).astype(np.float32)
    return hi24 * np.float32(2.0 ** -24)
