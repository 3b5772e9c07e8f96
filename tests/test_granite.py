"""Granite-3.0's four multipliers, checked against the plain float32
reference (``repro.models.reference_granite``) on seeded weights.

At a small size on the CPU in float32 (``configs.smoke.reduce`` keeps the
published multipliers): paged prefill then decode through ``PagedEngine``,
with a rebalance that resolves mid-decode, and the contiguous
``lm.prefill`` / ``lm.decode_step`` path agree with the reference's full
forward pass; setting any one multiplier back to identity fails that
comparison; and every other configuration computes exactly what it computed
before the multipliers existed.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.configs.smoke import reduce
from repro.core import LeapConfig
from repro.models import blocks, lm
from repro.models import reference_granite as ref
from repro.serving import engine as engine_mod
from repro.serving.engine import PagedConfig, PagedEngine

# Both sides compute in float32; only the order of the sums differs (the
# reference attends over the whole sequence at once, the engine page by page
# with an online softmax), which moves the logits by about 1e-6 of their
# norm.  A dropped multiplier moves them by more than 4e-3 of it here (the
# attention multiplier least: the 12x embedding dominates the residual
# stream of two layers).
TOL = 1e-4

PROMPT, STEPS = 9, 8  # the prompt crosses a 4-token page boundary


def _granite():
    return reduce(get_config("granite_3_2b"))


def _dims(cfg):
    return ref.Dims(
        hidden_size=cfg.d_model,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta,
        embedding_multiplier=cfg.embed_multiplier,
        attention_multiplier=cfg.attn_scale,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling,
    )


def _params(cfg, seed=0):
    """Seeded weights, with the norm weights (zero at init) perturbed so the
    norms' parameterisation is part of the comparison."""
    params = lm.init_params(jax.random.key(seed), cfg)
    keys = iter(jax.random.split(jax.random.key(seed + 1), 8))

    def perturb(path, x):
        name = getattr(path[-1], "key", "")
        if "norm" in str(name):
            return 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(perturb, params)


def _reference_weights(params, cfg):
    """The program's parameters in the reference's layout: the program's
    norms scale by ``1 + w``, the reference's (as Hugging Face's) by ``w``."""
    p = params["period"][0]
    layers = [
        {
            "input_norm": 1.0 + p["norm1"][i],
            "wq": p["attn"]["wq"][i],
            "wk": p["attn"]["wk"][i],
            "wv": p["attn"]["wv"][i],
            "wo": p["attn"]["wo"][i],
            "post_norm": 1.0 + p["norm2"][i],
            "w_gate": p["mlp"]["w_gate"][i],
            "w_up": p["mlp"]["w_in"][i],
            "w_down": p["mlp"]["w_out"][i],
        }
        for i in range(cfg.n_layers)
    ]
    return {"embed": params["embed"], "final_norm": 1.0 + params["final_norm"],
            "layers": layers}


def _rel_err(got, want):
    """Per row: |got - want| / |want| (L2), the largest over the rows."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)))


def _paged(cfg, params, prompt):
    """Prefill, then STEPS decode steps with a tick before each; a rebalance
    of the sequence to region 1 starts at step 2.  Returns the engine, the
    sequence, the rebalance's handle, the step at which it had resolved, and
    the logits of the prefill and of every step."""
    leap = LeapConfig(initial_area_blocks=2, budget_blocks_per_tick=2)
    eng = PagedEngine(cfg, params, PagedConfig(block_tokens=4, max_blocks_per_seq=16,
                                               n_regions=2, slots_per_region=64, leap=leap))
    sid = eng.admit(prompt, region=0)
    logits = [np.asarray(eng.last_logits[0])]
    handle, resolved_at = None, None
    for step in range(STEPS):
        if step == 2:
            handle = eng.rebalance(sid, dst_region=1)
        eng.tick()
        if handle is not None and handle.done and resolved_at is None:
            resolved_at = step
        eng.decode([sid])
        logits.append(np.asarray(eng.last_logits[0]))
    return eng, eng.seqs[sid], handle, resolved_at, np.stack(logits)


def _reference(cfg, params, tokens):
    return ref.forward(_reference_weights(params, cfg), np.asarray(tokens, np.int32), _dims(cfg))


@pytest.fixture(scope="module")
def granite():
    cfg = _granite()
    params = _params(cfg)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, size=PROMPT)
    return cfg, params, prompt


def test_reduced_granite_keeps_the_published_multipliers():
    full, small = get_config("granite_3_2b"), _granite()
    assert (full.embed_multiplier, full.attn_scale, full.residual_multiplier,
            full.logits_scaling, full.norm_eps) == (12.0, 0.015625, 0.22, 8.0, 1e-5)
    for f in ("embed_multiplier", "attn_scale", "residual_multiplier", "logits_scaling"):
        assert getattr(small, f) == getattr(full, f)
    assert small.compute_dtype == "float32"


def test_paged_decode_with_a_leap_matches_the_reference(granite):
    cfg, params, prompt = granite
    eng, seq, handle, resolved_at, logits = _paged(cfg, params, prompt)
    p = handle.progress()
    assert p.requested > 0 and p.committed + p.forced == p.requested
    assert resolved_at is not None and 2 < resolved_at < STEPS - 1  # resolved mid-decode
    assert (eng.facade.region_of(np.asarray(seq.block_ids, np.int32)) == 1).all()
    want, ks, vs = _reference(cfg, params, seq.tokens[:-1])
    assert _rel_err(logits, want[PROMPT - 1:]) < TOL
    # the pages hold the reference's keys and values, the appends included
    pages = np.asarray(eng.driver.read(np.asarray(seq.block_ids, np.int32), note=False))
    kv = np.moveaxis(pages, 0, 2)  # [L, 2, n_pages, BLK, KVH*hd]
    kv = kv.reshape(kv.shape[:2] + (-1, kv.shape[-1]))[:, :, : seq.length]
    for got, ref_kv in ((kv[:, 0], ks), (kv[:, 1], vs)):
        assert _rel_err(got, np.asarray(ref_kv).reshape(got.shape)) < TOL
    assert eng.stats.decode_steps == STEPS and eng.stats.tokens_prefilled == PROMPT
    assert eng.stats.kv_pages_read == sum(-(-(PROMPT + i + 1) // 4) for i in range(STEPS))


def test_contiguous_prefill_and_decode_match_the_reference(granite):
    cfg, params, prompt = granite
    max_len = PROMPT + STEPS
    logits, cache = lm.prefill(params, jnp.asarray(prompt)[None], cfg, max_len)
    tokens, rows = list(map(int, prompt)), [np.asarray(logits[0])]
    for i in range(STEPS):
        tokens.append(int(np.argmax(rows[-1])))
        logits, cache = lm.decode_step(params, cache, jnp.asarray([[tokens[-1]]], jnp.int32),
                                       jnp.asarray(PROMPT + i, jnp.int32), cfg)
        rows.append(np.asarray(logits[0]))
    want, _, _ = _reference(cfg, params, tokens)
    assert _rel_err(np.stack(rows), want[PROMPT - 1:]) < TOL


@pytest.mark.parametrize("identity", [
    {"embed_multiplier": 1.0},
    {"attn_scale": None},
    {"residual_multiplier": 1.0},
    {"logits_scaling": 1.0},
], ids=["embedding", "attention", "residual", "logits"])
def test_each_multiplier_left_out_fails_the_comparison(granite, identity):
    cfg, params, prompt = granite
    _, seq, _, _, logits = _paged(dataclasses.replace(cfg, **identity), params, prompt)
    want, _, _ = _reference(cfg, params, seq.tokens[:-1])
    assert _rel_err(logits, want[PROMPT - 1:]) > 10 * TOL


def _plain_add(x, y, cfg):
    return x + y


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS if a != "granite_3_2b"])
def test_other_configs_compute_what_they_did_without_multipliers(arch, monkeypatch):
    """At the identity defaults the multipliers leave no trace: prefill and
    one decode step give bit-identical logits to the same code with the
    residual helper replaced by the plain ``x + y`` it stands for."""
    cfg = reduce(get_config(arch))
    assert (cfg.embed_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (1, 1, 1)
    params = lm.init_params(jax.random.key(1), cfg)
    rng = np.random.default_rng(1)
    if cfg.embed_inputs:
        inputs = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 6)), jnp.int32)
        step_in = jnp.asarray([[3]], jnp.int32)
    else:
        inputs = jnp.asarray(rng.normal(size=(1, 6, cfg.d_model)), jnp.float32)
        step_in = jnp.asarray(rng.normal(size=(1, 1, cfg.d_model)), jnp.float32)

    def run():
        logits, cache = jax.jit(lambda p, t: lm.prefill(p, t, cfg, 8))(params, inputs)
        step, _ = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, jnp.int32(6), cfg))(
            params, cache, step_in)
        return np.asarray(logits), np.asarray(step)

    now = run()
    monkeypatch.setattr(blocks, "residual_add", _plain_add)
    plain = run()
    for a, b in zip(now, plain):
        assert np.array_equal(a, b)


def test_paged_step_without_multipliers_is_the_plain_step(monkeypatch):
    """The paged decode step of a configuration at the defaults is likewise
    unchanged by the residual helper."""
    cfg = dataclasses.replace(reduce(get_config("qwen2_7b")), n_layers=2)
    params = lm.init_params(jax.random.key(2), cfg)
    prompt = np.arange(7) % cfg.vocab_size

    def run():
        eng = PagedEngine(cfg, params, PagedConfig(block_tokens=4, max_blocks_per_seq=8,
                                                   n_regions=2, slots_per_region=32))
        sid = eng.admit(prompt)
        eng.decode([sid])
        return np.asarray(eng.last_logits)

    now = run()
    monkeypatch.setattr(engine_mod, "residual_add", _plain_add)
    engine_mod.decode_step_program.clear_cache()
    plain = run()
    engine_mod.decode_step_program.clear_cache()
    assert np.array_equal(now, plain)
