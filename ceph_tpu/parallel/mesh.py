"""Mesh construction helpers."""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the static varying-axes check: the
    Pallas kernels' outputs carry no varying-axes annotation (with the
    check on, a mesh program that runs one fails to trace for a TPU),
    and the reconstruct programs' outputs are replicated over the
    gather axis, which the checker cannot see through an all_gather."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def ec_shard_axis(k: int, n_devices: int) -> int:
    """Size of the EC mesh's 'shard' (chunk-layout) axis: the largest
    divisor of gcd(k, n) not exceeding 4, so survivor rows shard evenly
    for the reconstruct all-gather while most of the device count stays
    on the 'pg' axis for stripe/byte parallelism (an over-wide shard
    axis buys layout, not compute — encode work is stripe-sharded, and
    the reconstruct rebuild is byte-sharded over 'pg').

    Returns 1 when gcd(k, n) == 1 (prime k vs the device count) — the
    degenerate case MeshEcEngine's reconstruct handles by gathering
    over 'pg' instead (ISSUE 8 satellite)."""
    g = math.gcd(int(k), int(n_devices))
    for cand in (4, 3, 2):
        if g % cand == 0:
            return cand
    return 1


def make_mesh(
    n_devices: int | None = None,
    shard_parallelism: int | None = None,
    axis_names: tuple[str, str] = ("pg", "shard"),
) -> Mesh:
    """2-D mesh (pg, shard) over the first ``n_devices`` devices.

    ``shard_parallelism`` is the size of the chunk-sharding axis (must
    divide both n_devices and, at use sites, the k of the code); default:
    largest power of two <= min(4, n_devices).
    """
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if shard_parallelism is None:
        shard_parallelism = 1
        while (
            shard_parallelism * 2 <= 4
            and n % (shard_parallelism * 2) == 0
        ):
            shard_parallelism *= 2
    if n % shard_parallelism != 0:
        raise ValueError(
            f"shard_parallelism={shard_parallelism} does not divide {n} devices"
        )
    grid = np.array(devices).reshape(n // shard_parallelism, shard_parallelism)
    return Mesh(grid, axis_names)
