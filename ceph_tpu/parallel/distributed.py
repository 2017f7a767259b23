"""Distributed EC pipeline: sharded encode + ICI-collective reconstruction.

The multi-chip data path of the framework (the TPU-native analog of the
reference's k+m shard fan-out over the cluster messenger,
reference:src/osd/ECBackend.cc:1902-1926, and of recovery gathers,
reference:src/osd/ECBackend.cc:2187):

- encode: stripes are sharded over the ``pg`` mesh axis; each device
  encodes its stripes locally (no collectives — placement parallelism).
- degraded read / recovery: chunk rows live sharded over the ``shard``
  axis; surviving rows are all-gathered over ICI (`jax.lax.all_gather`
  inside `shard_map`) and the missing rows are rebuilt by the cached
  recovery matrix — the ICI collective replaces the MOSDECSubOpRead
  round-trips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import matrices as mx
from ..ops.gf import gf
from ..ops.gf_jax import make_gf_matmul


def _recovery_rows(parity: np.ndarray, k: int, w: int, present: list[int],
                   missing: list[int]) -> np.ndarray:
    """[len(missing), k] GF matrix over the first-k survivors."""
    G = gf(w)
    R = mx.decode_matrix(parity, k, w, present[:k])
    rows = []
    for r in missing:
        if r < k:
            rows.append(R[r])
        else:
            rows.append(G.matmul(parity[r - k][None, :], R)[0])
    return np.stack(rows)


def make_ec_step(
    mesh: Mesh,
    parity_matrix: np.ndarray,
    w: int = 8,
    erased: tuple[int, ...] = (0,),
):
    """Build a jitted distributed step: encode all stripes, then rebuild
    ``erased`` chunk rows from survivors via an all-gather over 'shard'.

    Input: data [S, k, C] uint8, sharded (pg, -, -); S divisible by the pg
    axis, k+m divisible by the shard axis for the reconstruct stage.
    Returns (full [S, k+m, C] sharded (pg, shard, -), rebuilt
    [S, len(erased), C] sharded (pg, -, -)).
    """
    parity_matrix = np.asarray(parity_matrix)
    m, k = parity_matrix.shape
    n = k + m
    present = [r for r in range(n) if r not in erased]
    if len(present) < k:
        raise ValueError("too many erasures")
    RM = _recovery_rows(parity_matrix, k, w, present, list(erased))

    enc = make_gf_matmul(parity_matrix, w)
    dec = make_gf_matmul(RM, w)

    def _flat(fn, x):  # x: [S, rows, C] -> fn over [rows, S*C]
        S, rows, C = x.shape
        flat = jnp.transpose(x, (1, 0, 2)).reshape(rows, S * C)
        out = fn(flat)
        return jnp.transpose(out.reshape(-1, S, C), (1, 0, 2))

    def local_encode(d):  # [S/pg, k, C] on one device
        parity = _flat(enc, d)
        return jnp.concatenate([d, parity], axis=1)

    def local_reconstruct(surv):  # [S/pg, k/shard_axis, C]
        g = jax.lax.all_gather(surv, "shard", axis=1, tiled=True)  # [S/pg, k, C]
        return _flat(dec, g)

    from .mesh import shard_map

    shard_encode = shard_map(
        local_encode, mesh,
        in_specs=P("pg", None, None), out_specs=P("pg", None, None),
    )
    # after the all_gather every 'shard' member computes the same rebuilt
    # rows (replicated output) — the static replication check can't see it
    shard_reconstruct = shard_map(
        local_reconstruct, mesh,
        in_specs=P("pg", "shard", None), out_specs=P("pg", None, None),
    )

    present_idx = jnp.array(present[:k])

    @jax.jit
    def step(data):
        full = shard_encode(data)
        # lay chunk rows out across the shard axis (positionally-distinct
        # roles, crush_choose_indep analog)
        full = jax.lax.with_sharding_constraint(
            full, NamedSharding(mesh, P("pg", "shard", None))
        )
        surv = jnp.take(full, present_idx, axis=1)
        rebuilt = shard_reconstruct(surv)
        return full, rebuilt

    return step


def encode_sharding(mesh: Mesh) -> NamedSharding:
    """Input sharding for make_ec_step's data argument."""
    return NamedSharding(mesh, P("pg", None, None))
