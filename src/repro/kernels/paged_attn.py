"""Pallas TPU kernel: paged flash-decode attention over a leap block table.

This is the serving hot path that *reads through* the migration-managed
indirection: the KV cache lives in a leap pool whose page payload is
``[L, 2, BLK, KVH*hd]`` (all layers of one token range; K and V with the
heads folded into the lane dim), and a per-sequence block table maps logical
KV blocks to physical slots.  Because decode reads go through the same table
the migrator flips, KV blocks can be leap-migrated between regions while
decode continues — reads before the flip hit the source slot, reads after hit
the destination; appends mark in-flight blocks dirty.

Kernel structure (one decode token per sequence, one layer per call):

  grid = (B, MAXB)               b: sequence, j: table position
  scalar prefetch: block table [B, MAXB] (drives the k/v BlockSpec index
  maps — the same indirection trick as the leap_copy kernel) and lens [B].
  VMEM scratch: fp32 running (acc[H, W], m[H, 1], l[H, 1]) online softmax
  per sequence; the j loop is innermost so the scratch carries across a
  sequence's blocks and is re-initialized at j == 0.

TPU shaping.  A K or V tile is the page's ``[BLK, W]`` slab (``W = KVH*hd``)
of the requested layer: its two minor dims are whole array dims, so the
BlockSpec is legal for any head count, and a 64-wide head never becomes a
lane-padded minor dim.  All heads are handled per grid step with a
block-diagonal query: row ``(h, g)`` of ``q_exp [H, W]`` holds query head
``h*G + g`` in lanes ``[h*hd, (h+1)*hd)`` and zeros elsewhere, so
``q_exp @ K^T`` is exactly the per-head score ``[H, BLK]``.  ``p @ V`` then
yields ``[H, W]`` whose diagonal ``hd``-blocks are the per-head outputs; the
wrapper extracts them.  The extra MXU work (KVH x) is free at decode's
arithmetic intensity, which is bound by the K/V bytes read.

Partial (out, m, l) are returned so sequence-sharded shards combine with a
log-sum-exp merge (``ref.combine_partials``).

Validated against ``ref.paged_decode_ref`` in interpret mode on CPU, and
compiled for a described v5e in tests/test_tpu_compile.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
_LANES = 128  # m/l partials are written lane-broadcast (dense stores)


def _decode_kernel(
    tables_ref,
    lens_ref,
    q_ref,  # [H, W] block-diagonal query
    k_ref,  # [BLK, W]
    v_ref,  # [BLK, W]
    out_ref,  # [H, W] f32
    mo_ref,  # [H, 128] f32
    lo_ref,  # [H, 128] f32
    acc_ref,  # VMEM [H, W] f32
    m_ref,  # VMEM [H, 1] f32
    l_ref,  # VMEM [H, 1] f32
    *,
    blk: int,
    softcap: float,
    scale: float,
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    ln = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * blk < ln)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale  # [H, W]
        k = k_ref[...].astype(jnp.float32)  # [BLK, W]
        v = v_ref[...].astype(jnp.float32)  # [BLK, W]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [H, BLK]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ln, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))  # [H, 1]
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [H, BLK]
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [H, W]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_ref[...]
        out_ref[...] = acc_ref[...] / l
        mo_ref[...] = jnp.broadcast_to(m_ref[...], mo_ref.shape)
        lo_ref[...] = jnp.broadcast_to(l, lo_ref.shape)


def paged_decode_pallas(
    q: jax.Array,  # [B, KVH, G, hd]
    kv_pool: jax.Array,  # [S, L, 2, BLK, KVH*hd]
    tables: jax.Array,  # [B, MAXB] int32, pad entries must be valid slot ids
    lens: jax.Array,  # [B] int32, >= 1
    *,
    layer: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns ``(out [B,KVH,G,hd], m [B,KVH,G], l [B,KVH,G])`` fp32 partials.

    Scores are ``scale * q.k`` (``scale`` None: ``1/sqrt(hd)``)."""
    b, kvh, g, hd = q.shape
    s, n_layers, two, blk, w = kv_pool.shape
    assert two == 2 and w == kvh * hd and 0 <= layer < n_layers, (
        q.shape,
        kv_pool.shape,
        layer,
    )
    h = kvh * g
    maxb = tables.shape[1]
    if scale is None:
        scale = 1.0 / (hd**0.5)
    eye = jnp.eye(kvh, dtype=q.dtype)
    q_exp = jnp.einsum("bkgd,kl->bkgld", q, eye).reshape(b, h, w)

    def kv_map(kv):
        # Past the sequence's last page, repeat it: an unchanged block index
        # is not fetched again, so padding costs no HBM traffic.
        def index(b, j, t, ln):
            last = (ln[b] - 1) // blk
            return (t[b, jnp.minimum(j, last)], layer, kv, 0, 0)

        return pl.BlockSpec((None, None, None, blk, w), index)

    row = pl.BlockSpec((None, h, w), lambda b, j, t, ln: (b, 0, 0))
    lanes = pl.BlockSpec((None, h, _LANES), lambda b, j, t, ln: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, maxb),
        in_specs=[row, kv_map(0), kv_map(1)],
        out_specs=[row, lanes, lanes],
        scratch_shapes=[
            pltpu.VMEM((h, w), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, blk=blk, softcap=float(softcap), scale=float(scale)
    )
    out_exp, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, w), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
        ],
        name="paged_decode",
        interpret=interpret,
    )(tables, lens, q_exp, kv_pool, kv_pool)
    # Row (k, g) of out_exp holds head k's output in lanes [k*hd, (k+1)*hd).
    heads = jnp.arange(kvh)
    out = out_exp.reshape(b, kvh, g, kvh, hd)[:, heads, :, heads, :]  # [KVH, B, G, hd]
    out = jnp.swapaxes(out, 0, 1).astype(q.dtype)
    return out, m[..., 0].reshape(b, kvh, g), l[..., 0].reshape(b, kvh, g)
