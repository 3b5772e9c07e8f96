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

  grid = (B,)                    one step per sequence, in order
  scalar prefetch: the layer, block table [B, MAXB] and lens [B]; the
  pool stays in HBM (``memory_space=pl.ANY``) and the kernel copies pages
  itself.  The layer is an operand, so a decode step's calls over its
  layers share one trace and lowering.
  Each step walks the ``ceil(len / BLK)`` pages its sequence holds in
  chunks of ``P`` pages (:func:`chunk_pages`, from the page slab's bytes).
  Each held page's K and V slabs of the requested layer, ``[2, BLK, W]``
  and contiguous in HBM, come in one async copy into a double-buffered
  VMEM scratch ``[2, P, 2, BLK, W]``: one copy a page (a copy for K and
  another for V took 26% longer on a TPU v5e at Granite-3.0-2B's shape),
  and a full chunk takes no branch per page.  The next chunk is in flight while the current
  one computes, and a sequence's last chunk prefetches the next sequence's
  first, so the copies never drain between sequences.  A partial last
  chunk copies only held pages; the V slots it leaves are zeroed so that a
  masked score never multiplies stale VMEM.  The fp32 online softmax
  ``(acc[H, W], m[H, 1], l[H, 1])`` carries across a sequence's chunks.
  Pages past a sequence's last are never touched, so the kernel's time
  follows the pages held, not ``MAXB``.

TPU shaping.  A K or V slab is the page's ``[BLK, W]`` tile (``W =
KVH*hd``) of the requested layer, so each copy is one dense transfer for
any head count, and a 64-wide head never becomes a lane-padded minor dim.
All heads are handled per chunk with a block-diagonal query: row ``(h, g)``
of ``q_exp [H, W]`` holds query head ``h*G + g`` in lanes
``[h*hd, (h+1)*hd)`` and zeros elsewhere, so ``q_exp @ K^T`` over the
chunk's ``[P*BLK, W]`` keys is exactly the per-head score ``[H, P*BLK]``.
``p @ V`` then yields ``[H, W]`` whose diagonal ``hd``-blocks are the
per-head outputs; the wrapper extracts them.  The extra MXU work (KVH x) is
cheap at decode's arithmetic intensity, which is bound by the K/V bytes
read.

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
# K bytes one chunk copies: a 16 KiB page slab alone is too small to stream
# HBM; a few hundred KiB is, and wastes little in a sequence's last chunk.
CHUNK_BYTES = 256 * 1024


def chunk_pages(blk: int, width: int, dtype, max_blocks: int) -> int:
    """Pages per chunk ``P`` of the kernel's walk over a sequence's pages,
    from the bytes of one page's ``[blk, width]`` K slab: 16 at Granite's
    16 KiB slab, more at smaller pages, never more than the table holds."""
    slab = blk * width * jnp.dtype(dtype).itemsize
    return max(1, min(max_blocks, CHUNK_BYTES // slab))


def _decode_kernel(
    layer_ref,  # SMEM [1]
    tables_ref,  # SMEM [B, MAXB]
    lens_ref,  # SMEM [B]
    q_ref,  # [H, W] block-diagonal query
    pool_ref,  # HBM [S, L, 2, BLK, W]
    out_ref,  # [H, W] f32
    mo_ref,  # [H, 128] f32
    lo_ref,  # [H, 128] f32
    kv_buf,  # VMEM [2 (buffer), P, 2 (K/V), BLK, W]
    sems,  # DMA [2 (buffer)]
    buf_ref,  # SMEM [1]: the buffer the next chunk to compute lands in
    *,
    pages: int,
    softcap: float,
    scale: float,
):
    b = pl.program_id(0)
    _, _, _, blk, w = kv_buf.shape
    maxb = tables_ref.shape[1]
    span = pages * blk  # key positions per chunk

    def held(i):
        return (lens_ref[i] + blk - 1) // blk

    def each_page(i, c, buf, on_held, on_missing=None):
        """``on_held(copy)`` for each page of chunk ``c`` of sequence ``i``
        that the sequence holds, ``on_missing(p)`` for each it does not.  A
        full chunk takes no branch per page."""
        n, first = held(i), c * pages
        cps = [
            pltpu.make_async_copy(
                pool_ref.at[tables_ref[i, jnp.minimum(first + p, maxb - 1)], layer_ref[0]],
                kv_buf.at[buf, p],
                sems.at[buf],
            )
            for p in range(pages)
        ]

        @pl.when(first + pages <= n)
        def _full():
            for cp in cps:
                on_held(cp)

        @pl.when(first + pages > n)
        def _partial():
            for p, cp in enumerate(cps):
                pl.when(first + p < n)(functools.partial(on_held, cp))
                if on_missing is not None:
                    pl.when(first + p >= n)(functools.partial(on_missing, p))

    def start(i, c, buf):
        def clear(p):  # so that a masked score never multiplies stale VMEM
            kv_buf[buf, p, 1] = jnp.zeros((blk, w), kv_buf.dtype)

        each_page(i, c, buf, lambda cp: cp.start(), clear)

    @pl.when(b == 0)
    def _first():
        buf_ref[0] = 0
        start(0, 0, 0)

    ln = lens_ref[b]
    n_chunks = (held(b) + pages - 1) // pages
    q = q_ref[...].astype(jnp.float32) * scale  # [H, W]
    h = q.shape[0]

    def chunk(c, carry):
        acc, m_prev, l_prev = carry
        buf = buf_ref[0]
        # prefetch the next chunk: this sequence's, or the next one's first
        last = c + 1 == n_chunks
        nxt_seq = jnp.where(last, b + 1, b)

        @pl.when(nxt_seq < pl.num_programs(0))
        def _prefetch():
            start(nxt_seq, jnp.where(last, 0, c + 1), 1 - buf)

        buf_ref[0] = 1 - buf
        each_page(b, c, buf, lambda cp: cp.wait())
        k = kv_buf[buf, :, 0].astype(jnp.float32).reshape(span, w)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [H, P*BLK]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = c * span + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ln, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))  # [H, 1]
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [H, P*BLK]
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = kv_buf[buf, :, 1].astype(jnp.float32).reshape(span, w)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [H, W]
        return acc * alpha + pv, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        0,
        n_chunks,
        chunk,
        (
            jnp.zeros((h, w), jnp.float32),
            jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
        ),
    )
    out_ref[...] = acc / l
    mo_ref[...] = jnp.broadcast_to(m, mo_ref.shape)
    lo_ref[...] = jnp.broadcast_to(l, lo_ref.shape)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def _walk(layer, tables, lens, q_exp, kv_pool, *, softcap, scale, interpret):
    """The kernel call.  The layer is an operand, so the decode step's calls
    over its layers share one trace and one lowering."""
    b, h, w = q_exp.shape
    blk = kv_pool.shape[3]
    pages = chunk_pages(blk, w, kv_pool.dtype, tables.shape[1])
    row = pl.BlockSpec((None, h, w), lambda b, *_: (b, 0, 0))
    lanes = pl.BlockSpec((None, h, _LANES), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row, lanes, lanes],
        scratch_shapes=[
            pltpu.VMEM((2, pages, 2, blk, w), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(_decode_kernel, pages=pages, softcap=softcap, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, w), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32),
        ],
        # a sequence's last chunk prefetches the next sequence's first: the
        # grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="paged_decode",
        interpret=interpret,
    )(layer, tables, lens, q_exp, kv_pool)


def paged_decode_pallas(
    q: jax.Array,  # [B, KVH, G, hd]
    kv_pool: jax.Array,  # [S, L, 2, BLK, KVH*hd]
    tables: jax.Array,  # [B, MAXB] int32; only held entries are read
    lens: jax.Array,  # [B] int32, >= 1
    *,
    layer: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns ``(out [B,KVH,G,hd], m [B,KVH,G], l [B,KVH,G])`` fp32 partials.

    Scores are ``scale * q.k`` (``scale`` None: ``1/sqrt(hd)``).  Entry ``j``
    of a sequence's table row is read only if the sequence holds page ``j``
    (``j * BLK < len``)."""
    b, kvh, g, hd = q.shape
    s, n_layers, two, blk, w = kv_pool.shape
    assert two == 2 and w == kvh * hd and 0 <= layer < n_layers, (
        q.shape,
        kv_pool.shape,
        layer,
    )
    h = kvh * g
    if scale is None:
        scale = 1.0 / (hd**0.5)
    eye = jnp.eye(kvh, dtype=q.dtype)
    q_exp = jnp.einsum("bkgd,kl->bkgld", q, eye).reshape(b, h, w)
    out_exp, m, l = _walk(
        jnp.full((1,), layer, jnp.int32), tables, lens, q_exp, kv_pool,
        softcap=float(softcap), scale=float(scale), interpret=interpret,
    )
    # Row (k, g) of out_exp holds head k's output in lanes [k*hd, (k+1)*hd).
    heads = jnp.arange(kvh)
    out = out_exp.reshape(b, kvh, g, kvh, hd)[:, heads, :, heads, :]  # [KVH, B, G, hd]
    out = jnp.swapaxes(out, 0, 1).astype(q.dtype)
    return out, m[..., 0].reshape(b, kvh, g), l[..., 0].reshape(b, kvh, g)
