"""The ``serve`` kind: a tiny Granite cell runs whole on the CPU, its
reference is the repository's, and broken programs come out ``correct:
false``.

The faults run a tiny copy of ``granite.decode_rebalance`` on the CPU.  The
sound program on six seeds, the controls (dirty tracking off, so a KV
append racing its page's copy is lost at the commit; the KV cache stored in
float8) and the readings of the comparison's limits also run at the cell's
own size on the chip, where they give each limit its two readings:

    PYTHONPATH=src:. python -m pytest chipbench/tests/test_serve.py \
        -k "sound or control or readings" --cell granite.decode_rebalance -s
"""

import dataclasses
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.driver as driver_mod
import repro.serving.engine as engine_mod
from chipbench import run, serve_work
from chipbench.kinds import serve
from chipbench.reference import granite as bench_ref
from conftest import ROOT, make_tiny_root
from repro.configs.base import get_config
from repro.models import lm
from repro.models import reference_granite as repo_ref
from test_faults import write_without_dirty_tracking

TINY_MODEL = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, vocab_size=128,
                  max_position_embeddings=64, page_tokens=4, slots_per_region=64,
                  leap={"initial_area_blocks": 4, "budget_blocks_per_tick": 4})
# every sequence tracked, so that a lost append shows within a short window
TINY_MIX = {"batch": 4, "prompt_lengths": {"8": 2, "12": 2}, "answer_tokens": 16,
            "stagger_tokens": 4, "tracked": 4, "min_tracked_leapt": 2}
CELL = "granite.decode_rebalance"
# the program's own, for the float8 control to wrap
PROJECT_QKV, CACHE_PAGES = engine_mod._project_qkv, engine_mod._cache_pages


def make_tiny_serve_root(dst):
    """``make_tiny_root`` plus ``granite.tiny``: the Granite cell at two
    layers of width 64, four sequences, 4-token pages."""
    root = make_tiny_root(dst)
    cb = root / "chipbench"
    conf = json.loads((cb / "configs" / "granite_3_2b.json").read_text())
    conf.update(TINY_MODEL)
    (cb / "configs" / "tiny_granite.json").write_text(json.dumps(conf))
    (cb / "traffic" / "tiny_decode.json").write_text(json.dumps(TINY_MIX))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_granite", "source": "test", "reduced": [],
                            "why": "test", "file": "chipbench/configs/tiny_granite.json"})
    spec["workloads"].append({"name": "granite.tiny", "config": "tiny_granite",
                              "traffic": "tiny_decode", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("granite.tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny_serve_root(tmp_path_factory):
    return make_tiny_serve_root(tmp_path_factory.mktemp("serve"))


def _run(root, workload="granite.tiny", seed=2147483659, seconds=1.0, trace=False,
         on_chip=False):
    return run.run_cell(root, workload, seed, seconds, trace, require_tpu=on_chip)


@pytest.fixture
def cell(request, tiny_serve_root):
    """(root, cell, on the chip, seconds): the cell named by ``--cell`` at its
    own size on the chip, else the tiny one on the CPU."""
    name = request.config.getoption("--cell")
    if name:
        return ROOT, name, True, 5.0
    return tiny_serve_root, "granite.tiny", False, 1.0


def test_tiny_cell_is_correct_and_reports_its_metrics(tiny_serve_root, capsys):
    e2e = _run(tiny_serve_root)
    assert e2e["correct"], e2e["checks"]
    assert set(e2e["metrics"]) == {"migrate_gib_s", "write_p95_ms", "setup_s"}
    assert e2e["metrics"]["migrate_gib_s"]["value"] > 0
    assert e2e["failed"] == 0 and e2e["attempted"] > 0
    traced = _run(tiny_serve_root, seed=5, trace=True)
    assert traced["correct"], traced["checks"]
    # the CPU has no device plane and no peaks: only the program's spans read
    assert set(traced["metrics"]) == {"decode_host_ms.granite", "admit_host_ms.granite",
                                      "rebalance_host_ms.granite"}
    assert all(v["value"] > 0 for v in traced["metrics"].values())
    assert "window_compiles=0 window_cache_loads=0" in capsys.readouterr().err


def test_bench_reference_is_the_repo_reference():
    cfg = json.loads((ROOT / "chipbench" / "configs" / "granite_3_2b.json").read_text())
    cfg.update(TINY_MODEL, num_hidden_layers=3)
    model = dataclasses.replace(serve.model_config(cfg), param_dtype="float32",
                                compute_dtype="float32")
    params = lm.init_params(jax.random.key(3), model)
    weights = {"embed": params["embed"], "final_norm": 1.0 + params["final_norm"],
               "layers": [serve.reference_weights(params, i) for i in range(3)]}
    ids = np.random.default_rng(3).integers(0, 128, size=11)
    bench = bench_ref.forward(weights, ids, bench_ref.Dims.from_config(cfg))
    repo = repo_ref.forward(weights, ids, repo_ref.Dims.from_config(cfg))
    for a, b in zip(bench, repo):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_configuration_is_the_repo_granite():
    cfg = json.loads((ROOT / "chipbench" / "configs" / "granite_3_2b.json").read_text())
    fields = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab_size",
              "layer_pattern", "mlp_kind", "tie_embeddings", "rope_theta", "norm_eps",
              "attn_scale", "embed_multiplier", "residual_multiplier", "logits_scaling",
              "param_dtype", "compute_dtype")
    mine, repo = serve.model_config(cfg), get_config("granite_3_2b")
    assert {f: getattr(mine, f) for f in fields} == {f: getattr(repo, f) for f in fields}
    assert mine.param_count() == 2_533_531_648
    page = cfg["page_tokens"]
    assert serve_work.paged_decode_bytes(cfg, 1, page, 2) == 1_310_720


def test_work_counts_match_the_shapes():
    cfg = json.loads((ROOT / "chipbench" / "configs" / "granite_3_2b.json").read_text())
    model = serve.model_config(cfg)
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]
    assert cfg["num_hidden_layers"] * serve_work.layer_params(cfg) == (
        model.param_count() - embed - norms)
    # one token over one key: every matrix product once, logits included
    assert serve_work.model_flops(cfg, 1, 1, 1) == (
        2 * (model.param_count() - norms) + serve_work.attention_flops(cfg, 1))
    # a 3-token prefill: three tokens through the layers, one through the
    # head, attending over 1 + 2 + 3 keys
    assert serve_work.model_flops(cfg, 3, 1, 6) == (
        3 * serve_work.model_flops(cfg, 1, 0, 0) + serve_work.model_flops(cfg, 0, 1, 0)
        + serve_work.attention_flops(cfg, 6))


@pytest.mark.parametrize("field", ["embed_multiplier", "residual_multiplier", "logits_scaling"])
def test_a_dropped_multiplier_is_not_correct(tiny_serve_root, monkeypatch, field):
    real = serve.model_config
    monkeypatch.setattr(serve, "model_config",
                        lambda c: dataclasses.replace(real(c), **{field: 1.0}))
    out = _run(tiny_serve_root, seed=7)
    assert not out["correct"]
    assert out["checks"]["logits_rel_err"]["value"] > out["checks"]["logits_rel_err"]["limit"]


@partial(jax.jit, static_argnames=("cfg", "blk"), donate_argnums=(1,))
def decode_without_dirty_tracking(params, state, tables, lens, toks, *, cfg, blk):
    """The decode step with its appends left unmarked: a page in flight keeps
    the dirty bit it had, so the append is lost when the page commits."""
    logits, new = engine_mod.paged_decode_step(params, state, tables, lens, toks, cfg=cfg,
                                               blk=blk)
    return logits, dataclasses.replace(new, dirty=state.dirty)


@pytest.mark.parametrize("seed", [3, 4, 2147483651])
def test_control_without_dirty_tracking_loses_kv_appends(cell, seed, monkeypatch):
    root, name, on_chip, seconds = cell
    monkeypatch.setattr(engine_mod, "decode_step_program", decode_without_dirty_tracking)
    monkeypatch.setattr(driver_mod, "leap_write", write_without_dirty_tracking)
    out = _run(root, name, seed, seconds, on_chip=on_chip)
    print(json.dumps({"cell": name, "seed": seed, "control": True, "correct": out["correct"],
                      "checks": out["checks"], "device": out["device"]}))
    assert not out["correct"]
    assert out["checks"]["kv_rel_err"]["value"] > out["checks"]["kv_rel_err"]["limit"]


def float8(x):
    """``x`` rounded to float8_e4m3fn (``reduce_precision``, not a cast there
    and back, which a compiler that allows excess precision may drop)."""
    fmt = jnp.finfo(jnp.float8_e4m3fn)
    return jax.lax.reduce_precision(x, exponent_bits=fmt.nexp, mantissa_bits=fmt.nmant)


def project_qkv_float8(x, params, cfg, positions):
    q, k, v = PROJECT_QKV(x, params, cfg, positions)
    return q, float8(k), float8(v)


def cache_pages_float8(cache, cfg, blk):
    return float8(CACHE_PAGES(cache, cfg, blk))


def decode_with_float8_kv(params, state, tables, lens, toks, *, cfg, blk):
    """The decode step, traced anew with ``project_qkv_float8`` in place: each
    new token's K and V are rounded before they are stored and attended."""
    return engine_mod.paged_decode_step(params, state, tables, lens, toks, cfg=cfg, blk=blk)


@pytest.mark.parametrize("seed", [2147494001, 2147494002, 2147494003, 2147494004,
                                  2147494005, 2147494006])
def test_sound_run_is_correct(cell, seed):
    root, name, on_chip, seconds = cell
    out = _run(root, name, seed, seconds, on_chip=on_chip)
    print(json.dumps({"cell": name, "seed": seed, "control": False, "correct": out["correct"],
                      "checks": out["checks"], "device": out["device"]}))
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [2147494101, 2147494102, 2147494103])
def test_control_with_the_kv_in_float8(cell, seed, monkeypatch):
    """A cache that stores its KV a precision below bf16: the pages the
    prefill installs and every token the decode step appends are rounded to
    float8_e4m3fn.  The first layer's KV error shows it."""
    root, name, on_chip, seconds = cell
    monkeypatch.setattr(engine_mod, "_project_qkv", project_qkv_float8)
    monkeypatch.setattr(engine_mod, "_cache_pages", cache_pages_float8)
    monkeypatch.setattr(engine_mod, "decode_step_program",
                        jax.jit(decode_with_float8_kv, static_argnames=("cfg", "blk"),
                                donate_argnums=(1,)))
    out = _run(root, name, seed, seconds, on_chip=on_chip)
    print(json.dumps({"cell": name, "seed": seed, "control": "float8 kv",
                      "correct": out["correct"], "checks": out["checks"],
                      "device": out["device"]}))
    assert not out["correct"]
    check = out["checks"]["kv_layer0_rel_err"]
    assert check["value"] > check["limit"]


def readings(cell) -> dict:
    """The logits limit's second readings, on the tracked sequence with the
    fewest tokens: the errors the reference gives with each multiplier left
    out (what a program that left it out would read)."""
    r = min((cell.reqs[s] for s in cell.tracked), key=lambda q: cell.engine.seqs[q.sid].length)
    d = cell.dims
    variants = {
        "without embedding_multiplier": dataclasses.replace(d, embedding_multiplier=1.0),
        "without attention_multiplier": dataclasses.replace(
            d, attention_multiplier=d.head_dim**-0.5),
        "without residual_multiplier": dataclasses.replace(d, residual_multiplier=1.0),
        "without logits_scaling": dataclasses.replace(d, logits_scaling=1.0),
    }
    return {name: cell._errors([r], dims) for name, dims in variants.items()}


def test_readings(cell, monkeypatch):
    """After a sound run, the reference's readings with a multiplier left
    out; at the cell's size each fails the logits limit (at two layers of
    width 64 the attention multiplier barely moves the result)."""
    root, name, on_chip, seconds = cell
    real, got = serve.Cell.check, {}

    def check_and_read(self):
        out = real(self)
        got.update(readings(self))
        return out

    monkeypatch.setattr(serve.Cell, "check", check_and_read)
    out = _run(root, name, 2147483701, seconds, on_chip=on_chip)
    print(json.dumps({"cell": name, "correct": out["correct"], "checks": out["checks"],
                      "readings": got, "device": out["device"]}))
    assert out["correct"] and len(got) == 4
    limit = out["checks"]["logits_rel_err"]["limit"]
    for name, errs in got.items():
        if on_chip or name != "without attention_multiplier":
            assert errs["logits_rel_err"] > limit, (name, errs, limit)
