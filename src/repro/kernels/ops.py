"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy: compiled Pallas on TPU, pure-jnp oracle elsewhere (CPU/GPU).
Tests force ``impl="pallas_interpret"`` to execute the kernel bodies in
Python on CPU and compare against the oracles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import leap_copy, paged_attn, ref


def _auto_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: str | None) -> tuple[str, bool]:
    impl = impl or "auto"
    if impl == "auto":
        impl = _auto_impl()
    if impl == "pallas_interpret":
        return "pallas", True
    return impl, False


# -- leap_copy ---------------------------------------------------------------
#
# The ``*_impl`` functions are the un-jitted dispatchers: the migrator's fused
# device programs (repro.core.migrator) call them from inside their own jit so
# TPU gets the HBM-to-HBM DMA kernels without a nested dispatch.  The jitted
# wrappers below remain the public standalone entry points.


def gather_blocks_impl(pool, idx, *, impl: str | None = None):
    """``pool[idx]``: pack migration blocks into a contiguous staging buffer."""
    kind, interp = _resolve(impl)
    if kind == "pallas":
        return leap_copy.gather_blocks_pallas(pool, idx, interpret=interp)
    return ref.gather_blocks_ref(pool, idx)


def scatter_blocks_impl(pool, idx, blocks, *, impl: str | None = None):
    """Unpack a staging buffer into pool slots."""
    kind, interp = _resolve(impl)
    if kind == "pallas":
        return leap_copy.scatter_blocks_pallas(pool, idx, blocks, interpret=interp)
    return ref.scatter_blocks_ref(pool, idx, blocks)


def copy_blocks_impl(pool, src_idx, dst_idx, *, impl: str | None = None):
    """Intra-pool block copy: ``pool[dst_idx[i]] = pool[src_idx[i]]``."""
    kind, interp = _resolve(impl)
    if kind == "pallas":
        return leap_copy.copy_blocks_pallas(pool, src_idx, dst_idx, interpret=interp)
    return ref.copy_blocks_ref(pool, src_idx, dst_idx)


def copy_runs_impl(pool, src_starts, dst_starts, *, run: int, impl: str | None = None):
    """Contiguous-run copy: one huge block (``run`` aligned slots) per step."""
    kind, interp = _resolve(impl)
    if kind == "pallas":
        return leap_copy.copy_runs_pallas(
            pool, src_starts, dst_starts, run, interpret=interp
        )
    return ref.copy_runs_ref(pool, src_starts, dst_starts, run)


gather_blocks = jax.jit(gather_blocks_impl, static_argnames=("impl",))
scatter_blocks = jax.jit(scatter_blocks_impl, static_argnames=("impl",), donate_argnums=(0,))
copy_blocks = jax.jit(copy_blocks_impl, static_argnames=("impl",), donate_argnums=(0,))
copy_runs = jax.jit(copy_runs_impl, static_argnames=("run", "impl"), donate_argnums=(0,))


# -- paged decode attention ----------------------------------------------------


_PAGED_STATIC = ("softcap", "scale", "kv_heads", "layer", "impl")


@functools.partial(jax.jit, static_argnames=_PAGED_STATIC)
def paged_decode(
    q,  # [B, H, hd]
    kv_pool,  # [S, L, 2, BLK, KVH*hd]
    tables,  # [B, MAXB]
    lens,  # [B]
    *,
    kv_heads: int,
    layer: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    impl: str | None = None,
):
    """One decode step of paged attention over ``layer``; returns ``out [B, H, hd]``.

    Scores are ``scale * q.k`` (``scale`` None: ``1/sqrt(hd)``)."""
    out, _, _ = paged_decode_partial(
        q, kv_pool, tables, lens, kv_heads=kv_heads, layer=layer, softcap=softcap,
        scale=scale, impl=impl,
    )
    return out


@functools.partial(jax.jit, static_argnames=_PAGED_STATIC)
def paged_decode_partial(
    q,
    kv_pool,
    tables,
    lens,
    *,
    kv_heads: int,
    layer: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    impl: str | None = None,
):
    """Paged decode returning flash partials ``(out, m, l)`` for shard combine."""
    b, h, hd = q.shape
    g = h // kv_heads
    assert g * kv_heads == h, (h, kv_heads)
    kind, interp = _resolve(impl)
    if kind == "pallas":  # the kernel reads only the entries of held pages
        qg = q.reshape(b, kv_heads, g, hd)
        out, m, l = paged_attn.paged_decode_pallas(
            qg, kv_pool, tables, lens, layer=layer, softcap=softcap, scale=scale,
            interpret=interp,
        )
        return out.reshape(b, h, hd), m.reshape(b, h), l.reshape(b, h)
    # the oracle gathers every entry: point the pad entries at a valid slot
    maxb = tables.shape[1]
    blk = kv_pool.shape[3]
    n_valid = (lens[:, None] + blk - 1) // blk
    safe_tables = jnp.where(
        jnp.arange(maxb)[None, :] < n_valid, tables, 0
    ).astype(jnp.int32)
    return ref.paged_decode_ref(
        q, kv_pool, safe_tables, lens, kv_heads=kv_heads, layer=layer, softcap=softcap,
        scale=scale,
    )


combine_partials = ref.combine_partials


# -- access-heat scan (closed-loop tiering) ------------------------------------


def heat_scan_impl(heat, ids, w, decay, *, impl: str | None = None):
    """Fused decay+accumulate over the per-block heat plane (un-jitted).

    Called from inside the megastep's jit (trace-time guarded on
    ``ids.shape[0]``, so the phase compiles away entirely when tiering is
    off); :func:`heat_scan` below is the standalone jitted entry point.
    ``ids`` lanes ``>= len(heat)`` are inert padding on both paths.
    """
    if ids.shape[0] == 0:
        return heat
    kind, interp = _resolve(impl)
    if kind == "pallas":
        from repro.kernels import heat_scan as heat_mod

        return heat_mod.heat_scan_pallas(heat, ids, w, decay, interpret=interp)
    return ref.heat_scan_ref(heat, ids, w, decay)


heat_scan = jax.jit(
    heat_scan_impl, static_argnames=("decay", "impl"), donate_argnums=(0,)
)


# -- RG-LRU scan -----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("impl", "chunk", "tile"))
def lru_scan(a, b, h0, *, impl: str | None = None, chunk: int = 8, tile: int = 128):
    """Blocked linear-recurrence scan (Griffin RG-LRU hot path)."""
    from repro.kernels import lru_scan as lru_mod

    kind, interp = _resolve(impl)
    if kind == "pallas":
        return lru_mod.lru_scan_pallas(a, b, h0, chunk=chunk, tile=tile, interpret=interp)
    return ref.lru_scan_ref(a, b, h0)
