"""Sweep tests: paged flash-decode Pallas kernel (interpret) vs jnp oracle,
plus the log-sum-exp shard-combine identity used by sequence-sharded decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.kernels.paged_attn import chunk_pages, paged_decode_pallas

N_LAYERS, LAYER = 2, 1

# (B, H, KVH, hd, BLK, MAXB, score scale: None is 1/sqrt(hd))
CASES = [
    (2, 4, 2, 64, 8, 4, None),
    (1, 8, 1, 128, 16, 3, None),  # MQA
    (3, 6, 6, 64, 8, 2, None),  # MHA
    (2, 12, 4, 128, 8, 5, None),  # GQA g=3
    (2, 8, 2, 64, 16, 3, 1 / 64),  # Granite's attention_multiplier, not 1/sqrt(64)
]


def _setup(b, h, kvh, hd, blk, maxb, dtype, seed=0):
    rng = np.random.default_rng(seed)
    s = b * maxb + 4
    q = jnp.asarray(rng.normal(size=(b, h, hd)), dtype)
    # page payload [L, 2, BLK, KVH*hd]; the tests attend over layer LAYER
    kv_pool = jnp.asarray(rng.normal(size=(s, N_LAYERS, 2, blk, kvh * hd)), dtype)
    # unique slots per sequence (a real block table never double-maps)
    slots = rng.choice(s, size=(b, maxb), replace=False)
    tables = jnp.asarray(slots, jnp.int32)
    lens = jnp.asarray(rng.integers(1, maxb * blk + 1, size=(b,)), jnp.int32)
    return q, kv_pool, tables, lens


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_matches_oracle(case, dtype):
    b, h, kvh, hd, blk, maxb, scale = case
    q, kv_pool, tables, lens = _setup(*case[:6], dtype)
    g = h // kvh
    out, m, l = paged_decode_pallas(
        q.reshape(b, kvh, g, hd), kv_pool, tables, lens, layer=LAYER, scale=scale,
        interpret=True,
    )
    want_out, want_m, want_l = ref.paged_decode_ref(
        q, kv_pool, tables, lens, kv_heads=kvh, layer=LAYER, scale=scale
    )
    if scale is not None:  # the scale reaches the scores
        _, plain_m, _ = ref.paged_decode_ref(q, kv_pool, tables, lens, kv_heads=kvh, layer=LAYER)
        assert not np.allclose(np.asarray(plain_m), np.asarray(want_m))
    np.testing.assert_allclose(
        np.asarray(out.reshape(b, h, hd), np.float32),
        np.asarray(want_out, np.float32),
        **_tol(dtype),
    )
    np.testing.assert_allclose(
        np.asarray(m.reshape(b, h)), np.asarray(want_m), **_tol(dtype)
    )
    np.testing.assert_allclose(
        np.asarray(l.reshape(b, h)), np.asarray(want_l), **_tol(dtype)
    )


# The chunk walk: (B, H, KVH, hd, BLK, MAXB, dtype, pages per chunk P).  The
# page slabs are large enough that P is small, so lengths fall at 1, P*BLK,
# P*BLK + 1, across three or more chunks, and near MAXB*BLK, where a table
# of 7 or 26 entries ends inside a chunk.
WALKS = [
    (5, 16, 8, 128, 32, 7, jnp.float32, 2),  # 128 KiB slabs
    (5, 16, 8, 128, 16, 26, jnp.bfloat16, 8),  # 32 KiB slabs
]


@pytest.mark.parametrize("poison", [False, True], ids=["clean", "poison"])
@pytest.mark.parametrize("walk", WALKS, ids=["f32_P2", "bf16_P8"])
def test_paged_decode_chunk_walk(walk, poison):
    """Sequences of one to many chunks, partial last chunks included, match
    the oracle.  Poisoned: every slot that no sequence holds within its first
    ``ceil(len / BLK)`` entries is NaN, and the output stays finite and
    unchanged, so the kernel copies only held pages.  The TPU interpreter
    fills VMEM with NaN and runs each copy at its wait, so a chunk that
    computes over a slot no copy filled shows too."""
    b, h, kvh, hd, blk, maxb, dtype, pages = walk
    assert chunk_pages(blk, kvh * hd, dtype, maxb) == pages
    q, kv_pool, tables, _ = _setup(b, h, kvh, hd, blk, maxb, dtype, seed=11)
    span = pages * blk
    lens = jnp.asarray([1, span, span + 1, 2 * span + blk + 1, maxb * blk - 1], jnp.int32)
    assert -(-int(lens[3]) // blk) > 2 * pages  # three chunks
    want_out, want_m, want_l = ref.paged_decode_ref(
        q, kv_pool, tables, lens, kv_heads=kvh, layer=LAYER
    )
    if poison:
        held = np.arange(maxb)[None, :] < -(-np.asarray(lens)[:, None] // blk)
        unheld = np.setdiff1d(np.arange(kv_pool.shape[0]), np.asarray(tables)[held])
        assert unheld.size  # the pad entries point at some of them
        kv_pool = kv_pool.at[unheld].set(jnp.nan)
    out, m, l = paged_decode_pallas(
        q.reshape(b, kvh, h // kvh, hd), kv_pool, tables, lens, layer=LAYER,
        interpret=pltpu.InterpretParams(),
    )
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(
        np.asarray(out.reshape(b, h, hd), np.float32),
        np.asarray(want_out, np.float32),
        **_tol(dtype),
    )
    np.testing.assert_allclose(np.asarray(m.reshape(b, h)), np.asarray(want_m), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(l.reshape(b, h)), np.asarray(want_l), **_tol(dtype))


def test_paged_decode_softcap():
    case = (2, 4, 2, 64, 8, 4)
    q, kv_pool, tables, lens = _setup(*case, jnp.float32, seed=7)
    b, h, kvh, hd, blk, maxb = case
    out, m, l = paged_decode_pallas(
        q.reshape(b, kvh, h // kvh, hd), kv_pool, tables, lens,
        softcap=20.0, layer=LAYER, interpret=True,
    )
    want, _, _ = ref.paged_decode_ref(
        q, kv_pool, tables, lens, softcap=20.0, kv_heads=kvh, layer=LAYER
    )
    np.testing.assert_allclose(
        np.asarray(out.reshape(b, h, hd)), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    # softcap must actually change the result
    plain, _, _ = ref.paged_decode_ref(q, kv_pool, tables, lens, kv_heads=kvh, layer=LAYER)
    assert not np.allclose(np.asarray(want), np.asarray(plain))


def test_paged_decode_single_token_sequences():
    b, h, kvh, hd, blk, maxb = 2, 4, 2, 64, 8, 4
    q, kv_pool, tables, _ = _setup(b, h, kvh, hd, blk, maxb, jnp.float32, seed=3)
    lens = jnp.ones((b,), jnp.int32)  # attention over exactly one token
    out, m, l = paged_decode_pallas(
        q.reshape(b, kvh, h // kvh, hd), kv_pool, tables, lens, layer=LAYER, interpret=True
    )
    want, _, _ = ref.paged_decode_ref(q, kv_pool, tables, lens, kv_heads=kvh, layer=LAYER)
    np.testing.assert_allclose(
        np.asarray(out.reshape(b, h, hd)), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    # l must be exactly 1 (softmax over a single position)
    np.testing.assert_allclose(np.asarray(l), 1.0, rtol=1e-6)


def test_shard_combine_identity():
    """Splitting a sequence's blocks across P shards and LSE-combining the
    partials must equal unsharded attention (the sequence-sharded decode path)."""
    b, h, kvh, hd, blk, maxb = 2, 8, 2, 64, 8, 6
    q, kv_pool, tables, _ = _setup(b, h, kvh, hd, blk, maxb, jnp.float32, seed=9)
    lens = jnp.full((b,), maxb * blk, jnp.int32)
    full, _, _ = ref.paged_decode_ref(q, kv_pool, tables, lens, kv_heads=kvh, layer=LAYER)
    # shard the table into 2 halves of 3 blocks
    outs, ms, ls = [], [], []
    for p in range(2):
        tab = tables[:, p * 3 : (p + 1) * 3]
        ln = jnp.full((b,), 3 * blk, jnp.int32)
        o, m, l = ref.paged_decode_ref(q, kv_pool, tab, ln, kv_heads=kvh, layer=LAYER)
        outs.append(o), ms.append(m), ls.append(l)
    combined = ref.combine_partials(
        jnp.stack(outs), jnp.stack(ms), jnp.stack(ls)
    )
    np.testing.assert_allclose(np.asarray(combined), np.asarray(full), rtol=2e-5, atol=2e-5)


def test_ops_paged_decode_wrapper():
    b, h, kvh, hd, blk, maxb = 2, 4, 2, 64, 8, 4
    q, kv_pool, tables, lens = _setup(b, h, kvh, hd, blk, maxb, jnp.float32, seed=5)
    # pad entries deliberately out of range: wrapper must sanitize them
    n_valid = (np.asarray(lens) + blk - 1) // blk
    tab = np.asarray(tables).copy()
    for i in range(b):
        tab[i, n_valid[i] :] = 10**6
    out_ref_impl = ops.paged_decode(
        q, kv_pool, jnp.asarray(tab), lens, kv_heads=kvh, layer=LAYER, impl="ref"
    )
    out_pallas = ops.paged_decode(
        q, kv_pool, jnp.asarray(tab), lens, kv_heads=kvh, layer=LAYER, impl="pallas_interpret"
    )
    np.testing.assert_allclose(
        np.asarray(out_pallas), np.asarray(out_ref_impl), rtol=2e-5, atol=2e-5
    )
