"""Pallas TPU kernel: per-block access-heat decay + accumulate (one pass).

The closed-loop tiering plane (DESIGN.md §13) maintains one exponentially
decayed heat counter per block on device:

    heat' = heat * decay;  heat'[ids[k]] += w[k]   for every access sample

A tick's samples arrive as a flat ``(ids, w)`` batch (reads weight 1.0,
writes ``LeapConfig.tier_write_weight``); the whole update is ONE pass over
the heat plane so it can ride the megastep without adding a dispatch.

TPU shaping: the heat plane is stored as a flat ``[L]`` fp32 vector with
``L`` a multiple of 1024 (= 8 sublanes x 128 lanes, see
:func:`padded_heat_len`); the kernel views it as ``[L/128, 128]`` and grids
over row tiles x sample chunks.  Scatter is not a Pallas primitive, so the
accumulate is a one-hot matmul that uses only 2-D compares: for a tile of
``TR`` rows and a chunk of ``KC`` samples,

    A[r, k]  = w[k] if row(ids[k]) == tile_row0 + r else 0     [TR, KC]
    Bt[c, k] = 1    if col(ids[k]) == c                         [128, KC]
    tile    += A @ Bt^T                                         [TR, 128]

Every memory access is dense and aligned, and the MXU does the summing.
Sample ids live in VMEM as a ``[1, K]`` lane row, padded to the chunk with
the out-of-bounds sentinel ``L`` (its row is past every tile, so a padded
lane contributes nothing — the same drop semantics as the jnp oracle's
``mode="drop"`` scatter).

Validated against :func:`repro.kernels.ref.heat_scan_ref` in interpret mode
on CPU (tests/test_tiering.py), and compiled for a described v5e in
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_LANES = 128
_SUBLANES = 8
_TILE = _LANES * _SUBLANES  # heat-plane length granule
_MAX_TILE_ROWS = 256  # rows of the heat plane per grid step
_CHUNK = 512  # samples per grid step


def padded_heat_len(n_blocks: int) -> int:
    """Smallest multiple of 1024 (8 sublanes x 128 lanes) holding n_blocks."""
    return max(1, (max(n_blocks, 1) + _TILE - 1) // _TILE) * _TILE


def _heat_kernel(ids_ref, w_ref, heat_ref, out_ref, *, decay: float, tile_rows: int):
    i = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _decay():
        out_ref[...] = heat_ref[...] * decay

    ids = ids_ref[...]  # [1, KC] (sentinel lanes fall past every tile)
    kc = ids.shape[1]
    row = jnp.right_shift(ids, 7) - i * tile_rows  # ids >= 0: shift = // 128
    col = jnp.bitwise_and(ids, _LANES - 1)
    a = jnp.where(
        lax.broadcasted_iota(jnp.int32, (tile_rows, kc), 0) == row, w_ref[...], 0.0
    )
    bt = (lax.broadcasted_iota(jnp.int32, (_LANES, kc), 0) == col).astype(jnp.float32)
    out_ref[...] += lax.dot_general(
        a,
        bt,
        (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def heat_scan_pallas(
    heat: jax.Array,  # [L] f32, L % 1024 == 0
    ids: jax.Array,  # [K] int32 (sentinel >= L = no-op lane)
    w: jax.Array,  # [K] f32
    decay: float,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused decay+accumulate over the flat heat plane; returns new heat."""
    (l,) = heat.shape
    assert l % _TILE == 0, l
    rows = l // _LANES
    tile_rows = math.gcd(rows, _MAX_TILE_ROWS)
    k = ids.shape[0]
    kc = min(_CHUNK, max(_LANES, -(-k // _LANES) * _LANES))
    kp = -(-k // kc) * kc
    if kp != k:
        # id = L lies past every row tile; weight 0 keeps the lane inert anyway
        ids = jnp.concatenate([ids, jnp.full((kp - k,), l, ids.dtype)])
        w = jnp.concatenate([w, jnp.zeros((kp - k,), w.dtype)])
    samples = pl.BlockSpec((1, kc), lambda i, c: (0, c))
    tile = pl.BlockSpec((tile_rows, _LANES), lambda i, c: (i, 0))
    out = pl.pallas_call(
        functools.partial(_heat_kernel, decay=float(decay), tile_rows=tile_rows),
        grid=(rows // tile_rows, kp // kc),
        in_specs=[samples, samples, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        input_output_aliases={2: 0},
        name="heat_scan",
        interpret=interpret,
    )(
        ids.reshape(1, kp).astype(jnp.int32),
        w.reshape(1, kp).astype(jnp.float32),
        heat.reshape(rows, _LANES).astype(jnp.float32),
    )
    return out.reshape(l)
