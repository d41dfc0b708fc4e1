"""Device mesh construction for ensemble sharding.

Meshes are built by functions (never module constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax


def make_markets_mesh(devices=None, skip=()):
    """1-D mesh over the market (ensemble) axis for sharded simulation runs.

    ``devices`` selects how many local devices to span (default: all). The
    simulator's market axis is embarrassingly parallel — independent markets,
    no collectives — so a plain 1-D ``("markets",)`` mesh is the whole
    topology. Works identically on real TPU slices and on CPU runners forced
    to N host devices via ``XLA_FLAGS=--xla_force_host_platform_device_count``.

    ``skip`` excludes local device *indices* before selection — the elastic
    rebuild path after a device loss: ``make_markets_mesh(skip=(2,))``
    spans every surviving device, and a snapshot restored onto the new mesh
    resumes the stream bitwise (snapshots are layout-portable).
    """
    skip = frozenset(int(i) for i in skip)
    avail = [d for i, d in enumerate(jax.devices()) if i not in skip]
    if not avail:
        raise ValueError(f"skip={sorted(skip)} excludes every local device")
    if devices is None:
        devices = len(avail)
    n = int(devices)
    if not (1 <= n <= len(avail)):
        raise ValueError(
            f"requested {n} devices; have {len(avail)} "
            "(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "forces N host devices on CPU)")
    return jax.make_mesh((n,), ("markets",), devices=avail[:n],
                         axis_types=(jax.sharding.AxisType.Auto,))


# TPU v5e hardware model used by the roofline analysis (EXPERIMENTS.md §Roofline)
HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_link_bw": 50e9,         # bytes/s per link per direction
    "ici_links_per_axis": 2,     # 2D torus: 2 links per mesh axis
    "hbm_per_chip": 16e9,
}
