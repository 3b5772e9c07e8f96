"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are also the implementations used on CPU/GPU backends where the TPU
kernels don't lower (``ops.py`` dispatches).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# -- leap_copy ---------------------------------------------------------------


def gather_blocks_ref(pool: jax.Array, idx: jax.Array) -> jax.Array:
    return pool[idx]


def scatter_blocks_ref(pool: jax.Array, idx: jax.Array, blocks: jax.Array) -> jax.Array:
    return pool.at[idx].set(blocks)


def copy_blocks_ref(pool: jax.Array, src_idx: jax.Array, dst_idx: jax.Array) -> jax.Array:
    return pool.at[dst_idx].set(pool[src_idx])


def copy_runs_ref(
    pool: jax.Array, src_starts: jax.Array, dst_starts: jax.Array, run: int
) -> jax.Array:
    """Contiguous-run copy oracle (starts must be ``run``-aligned)."""
    s = pool.shape[0]
    grouped = pool.reshape((s // run, run) + pool.shape[1:])
    grouped = grouped.at[dst_starts // run].set(grouped[src_starts // run])
    return grouped.reshape(pool.shape)


# -- paged decode attention ---------------------------------------------------


def paged_decode_ref(
    q: jax.Array,  # [B, H, hd]
    kv_pool: jax.Array,  # [S, L, 2, BLK, KVH*hd]
    tables: jax.Array,  # [B, MAXB] int32 slot ids (padded arbitrarily)
    lens: jax.Array,  # [B] int32 tokens per sequence
    *,
    kv_heads: int,
    layer: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Oracle: full-precision paged attention over one layer of the pool.

    Scores are ``scale * q.k`` (``scale`` None: ``1/sqrt(hd)``).

    Returns ``(out [B,H,hd], m [B,H], l [B,H])`` where m/l are the softmax
    running max and normalizer (fp32) so that shard partials combine as::

        m* = max_i m_i;  l* = sum_i l_i exp(m_i - m*)
        out* = sum_i out_i l_i exp(m_i - m*) / l*
    """
    b, h, hd = q.shape
    blk = kv_pool.shape[3]
    kvh = kv_heads
    maxb = tables.shape[1]
    g = h // kvh
    if scale is None:
        scale = 1.0 / (hd**0.5)

    def per_seq(qb, tab, ln):
        k = kv_pool[tab, layer, 0].reshape(maxb * blk, kvh, hd).astype(jnp.float32)
        v = kv_pool[tab, layer, 1].reshape(maxb * blk, kvh, hd).astype(jnp.float32)
        qg = (qb.astype(jnp.float32) * scale).reshape(kvh, g, hd)
        scores = jnp.einsum("kgd,tkd->kgt", qg, k)  # [KVH, G, T]
        if softcap:
            scores = softcap * jnp.tanh(scores / softcap)
        valid = jnp.arange(maxb * blk) < ln
        scores = jnp.where(valid[None, None, :], scores, -jnp.inf)
        m = jnp.max(scores, axis=-1)  # [KVH, G]
        p = jnp.exp(scores - m[..., None])
        l = jnp.sum(p, axis=-1)  # [KVH, G]
        out = jnp.einsum("kgt,tkd->kgd", p, v) / l[..., None]
        return (
            out.reshape(h, hd).astype(q.dtype),
            m.reshape(h),
            l.reshape(h),
        )

    return jax.vmap(per_seq)(q, tables, lens)


def combine_partials(
    outs: jax.Array,  # [P, B, H, hd] per-shard partial outputs
    ms: jax.Array,  # [P, B, H]
    ls: jax.Array,  # [P, B, H]
) -> jax.Array:
    """Merge flash partials from P shards (sequence-sharded KV)."""
    m_star = jnp.max(ms, axis=0)  # [B, H]
    w = ls * jnp.exp(ms - m_star[None])  # [P, B, H]
    l_star = jnp.sum(w, axis=0)
    out = jnp.sum(outs.astype(jnp.float32) * w[..., None], axis=0) / l_star[..., None]
    return out.astype(outs.dtype)


# -- access-heat scan (closed-loop tiering) -----------------------------------


def heat_scan_ref(
    heat: jax.Array,  # [L] f32 per-block heat (L = padded_heat_len(n_blocks))
    ids: jax.Array,  # [K] int32 accessed block ids (sentinel >= L = no-op lane)
    w: jax.Array,  # [K] f32 per-access weight (reads 1.0, writes cfg-weighted)
    decay: float,
) -> jax.Array:
    """Oracle: one fused decay+accumulate pass over the heat plane.

    ``heat' = heat * decay  then  heat'[ids[k]] += w[k]`` for every sample.
    Out-of-bounds ids are dropped (``mode="drop"``), which is exactly how the
    dispatch stage pads sample batches to their bucket — a padded lane is a
    sentinel id ``>= L`` with weight 0 and performs no update.
    """
    heat = heat.astype(jnp.float32) * jnp.float32(decay)
    return heat.at[ids].add(w.astype(jnp.float32), mode="drop")


# -- RG-LRU linear-recurrence scan ---------------------------------------------


def lru_scan_ref(a: jax.Array, b: jax.Array, h0: jax.Array) -> jax.Array:
    """Oracle for the blocked LRU scan: h_t = a_t h_{t-1} + b_t."""

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    a32 = a.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    b32 = b32.at[:, 0].add(a32[:, 0] * h0.astype(jnp.float32))
    _, h = jax.lax.associative_scan(combine, (a32, b32), axis=1)
    return h.astype(a.dtype)
