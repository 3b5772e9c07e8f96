"""Granite-3.0 decoding from a paged KV cache whose pages leap between regions.

The system under test is ``repro.serving.engine.PagedEngine`` on its normal
path: ``admit`` (prefill and page install), ``decode`` (one token for every
running sequence, through the paged-attention kernel), one migration
``tick`` before each decode step, and ``rebalance`` (a sequence's KV pages
leap to another region while it keeps decoding).

The loop is closed: ``batch`` sequences always run.  Each decodes
``answer_tokens`` steps after its prefill, is released, and a request with
a prompt of the same length and fresh tokens takes its slot.  Slot ``i``
starts the window ``i * stagger_tokens`` into its answer, so one request
finishes every ``stagger_tokens`` steps; set-up decodes up to there.  Every
running sequence leaps to the other region, and back as soon as that leap
resolves; a finished sequence's leap is cancelled as it is released.

Configuration keys: the model's ``config.json`` numbers (``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``vocab_size``, ``max_position_embeddings``,
``rms_norm_eps``, ``rope_theta``, the four multipliers, ``torch_dtype``),
``page_tokens``, ``n_regions``, ``slots_per_region``, ``leap``
(``LeapConfig`` fields) and ``tolerances``.  Mix keys: ``batch``,
``prompt_lengths`` (length -> count: the batch's prompts, dealt to its slots
in the seed's order), ``answer_tokens``, ``stagger_tokens``, ``tracked``
(slots whose last sequence is compared with the reference) and
``min_tracked_leapt``.

The window's facts are the pool kind's migration counts (``ticks``,
``committed_blocks``, ``block_bytes``, ``bytes_copied``, ``useful_bytes``)
and the serving ones: the model operations of the window (matrix products
of the tokens the program counts as decoded and prefilled, attention over
the keys this kind counts) and the KV bytes the paged kernel must read
(from the program's count of pages read).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import serve_work
from chipbench.reference import granite as ref
from repro.configs.base import ModelConfig
from repro.core import LeapConfig
from repro.models import lm
from repro.serving.engine import PagedConfig, PagedEngine

GIB = float(1 << 30)
F32 = jnp.float32
# The numbers compared with the reference, each against its tolerance.  The
# first layer's KV is the one number bf16 rounding through the layers does
# not swamp: its error is the storage format's and one projection's.
ERRORS = ("logits_rel_err", "kv_rel_err", "kv_layer0_rel_err")

def model_config(c: dict) -> ModelConfig:
    """The program's configuration for the published numbers ``c``."""
    if (c["hidden_act"], c["attention_bias"], c["mlp_bias"], c["tie_word_embeddings"]) != (
            "silu", False, False, True):
        raise ValueError("the serve kind runs a bias-free SwiGLU decoder with a tied head")
    heads = c["num_attention_heads"]
    return ModelConfig(
        name=c["model_type"],
        family="dense",
        n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        n_heads=heads,
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // heads,
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        layer_pattern=("attn",),
        mlp_kind="swiglu",
        tie_embeddings=True,
        rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        attn_scale=c["attention_multiplier"],
        embed_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        logits_scaling=c["logits_scaling"],
        param_dtype=c["torch_dtype"],
        compute_dtype=c["torch_dtype"],
    )


def reference_weights(params, layer: int) -> dict:
    """One layer of the program's parameters in the reference's layout (the
    program's norms scale by ``1 + w``, the reference's by ``w``)."""
    p = params["period"][0]
    return {
        "input_norm": 1.0 + p["norm1"][layer].astype(F32),
        "wq": p["attn"]["wq"][layer],
        "wk": p["attn"]["wk"][layer],
        "wv": p["attn"]["wv"][layer],
        "wo": p["attn"]["wo"][layer],
        "post_norm": 1.0 + p["norm2"][layer].astype(F32),
        "w_gate": p["mlp"]["w_gate"][layer],
        "w_up": p["mlp"]["w_in"][layer],
        "w_down": p["mlp"]["w_out"][layer],
    }


def qk_gain(c: dict) -> float:
    """Scale of the random query and key weights against ``lm.init_params``'.

    At that init, q.k / sqrt(head_dim) has unit spread; Granite scores
    q.k * attention_multiplier (1/64, not 1/8), so its attention would be
    near uniform and leaving the multiplier out would hardly show.  Scaling
    wq and wk by this gain each gives its scores unit spread again."""
    head_dim = c["hidden_size"] // c["num_attention_heads"]
    return (c["attention_multiplier"] * head_dim**0.5) ** -0.5


@partial(jax.jit, donate_argnums=0)
def scale_weights(w, gain):
    return (w.astype(F32) * gain).astype(w.dtype)


@jax.jit
def row_rel_err(got, want):
    """Largest over the rows of |got - want| / |want| (L2 over the last axis)."""
    got, want = got.astype(F32), want.astype(F32)
    num = jnp.sqrt(jnp.sum((got - want) ** 2, axis=-1))
    return jnp.max(num / jnp.maximum(jnp.sqrt(jnp.sum(want * want, axis=-1)), 1e-30))


@jax.jit
def take_rows(x, rows):
    return x[rows]


@dataclasses.dataclass
class Request:
    sid: int
    budget: int  # decode steps it runs
    steps: int = 0
    steps_leaping: int = 0  # decode steps taken while one of its leaps was in flight
    rows: list = dataclasses.field(default_factory=list)  # (position, logits) if tracked


class Cell:
    def __init__(self, cfg, mix, seed, devices, spans, log=print):
        self.cfg, self.mix, self.seed, self.devices = cfg, mix, seed, devices
        self.spans, self.log = spans, log
        self.model = model_config(cfg)
        self.dims = ref.Dims.from_config(cfg)
        self.page = int(cfg["page_tokens"])
        self.batch = int(mix["batch"])
        self.answer = int(mix["answer_tokens"])
        self.stagger = int(mix["stagger_tokens"])
        self.rng = np.random.default_rng([seed, 2])

    # -- set-up -----------------------------------------------------------------

    def setup(self) -> None:
        cfg, mix = self.cfg, self.mix
        params = jax.jit(lm.init_params, static_argnums=1)(
            jax.random.key(self.seed % 2**32), self.model)
        attn = params["period"][0]["attn"]
        for name in ("wq", "wk"):
            attn[name] = scale_weights(attn[name], qk_gain(cfg))
        pcfg = PagedConfig(
            block_tokens=self.page,
            max_blocks_per_seq=cfg["max_position_embeddings"] // self.page,
            n_regions=cfg["n_regions"],
            slots_per_region=cfg["slots_per_region"],
            leap=LeapConfig(**cfg.get("leap", {})),
        )
        self.engine = PagedEngine(self.model, params, pcfg)
        self.driver = self.engine.driver
        self.page_bytes = self.engine.pool_cfg.block_bytes
        lengths = [int(n) for n, k in mix["prompt_lengths"].items() for _ in range(k)]
        if len(lengths) != self.batch:
            raise ValueError(f"{len(lengths)} prompt lengths for a batch of {self.batch}")
        self.prompt_len = self.rng.permutation(lengths)
        self.tracked = np.sort(self.rng.choice(self.batch, mix["tracked"], replace=False))
        self.handles: list = []  # every leap started
        self.leaps: dict = {}  # sid -> its latest leap
        self.boundaries: list = []  # (page ids, host table rows, device table copy)
        self.keys = 0  # keys attended over, every decoded and prefilled token
        self.admissions = 0
        self.peak_pages = 0
        # Slot i's sequence at the start of the window began `warm - stagger*i`
        # steps into set-up; the first sequence of each slot runs up to there.
        warm = (self.batch - 1) * self.stagger
        self.reqs: list = [None] * self.batch
        for slot in range(self.batch):
            self._admit(slot, warm - self.stagger * slot or self.answer)
        for _ in range(warm):
            self._step()
        jax.block_until_ready(self.driver.state)

    def _admit(self, slot: int, budget: int) -> None:
        eng = self.engine
        n = int(self.prompt_len[slot])
        prompt = self.rng.integers(0, self.cfg["vocab_size"], size=n, dtype=np.int32)
        with self.spans.span("write"):
            sid = eng.admit(prompt, region=slot % eng.pcfg.n_regions)
        self.keys += n * (n + 1) // 2
        self.admissions += 1
        req = self.reqs[slot] = Request(sid, budget)
        if slot in self.tracked:
            req.rows.append((n - 1, np.asarray(eng.last_logits[0])))
        self._leap(sid)

    def _leap(self, sid: int) -> None:
        """Start the sequence's next leap, to the region it is not homed on."""
        seq = self.engine.seqs[sid]
        h = self.engine.rebalance(sid, (seq.region + 1) % self.engine.pcfg.n_regions)
        self.leaps[sid] = h
        self.handles.append(h)

    def _boundary(self, sid: int) -> None:
        """A sequence's leap resolved: keep the host's rows of its pages that
        no leap holds, and a copy of the device's table (nothing waits on it),
        for check()."""
        ids = np.asarray(self.engine.seqs[sid].block_ids, np.int32)
        ids = ids[~self.driver.in_migration(ids)]
        self.boundaries.append((ids, self.driver.host_table()[ids],
                                jnp.copy(self.driver.state.table)))

    def _step(self) -> float:
        """One tick, the next leaps where leaps resolved, one decode step of
        the whole batch (issued until its tokens are on the host), then the
        finished sequences' replacements.  Returns the decode step's latency
        in seconds."""
        eng = self.engine
        with self.spans.span("tick"):
            eng.tick()
        for req in self.reqs:
            if self.leaps[req.sid].done:
                self._boundary(req.sid)
                self._leap(req.sid)
        sids = [r.sid for r in self.reqs]
        contexts = [eng.seqs[s].length + 1 for s in sids]
        leaping = [not self.leaps[s].done for s in sids]
        t0 = time.perf_counter()
        with self.spans.span("write"):
            eng.decode(sids)
        dt = time.perf_counter() - t0
        self.keys += sum(contexts)
        rows = np.asarray(take_rows(eng.last_logits, self.tracked))
        for j, slot in enumerate(self.tracked):
            self.reqs[slot].rows.append((contexts[slot] - 1, rows[j]))
        self.peak_pages = max(self.peak_pages, sum(len(eng.seqs[s].block_ids) for s in sids))
        for slot, req in enumerate(self.reqs):
            req.steps += 1
            req.steps_leaping += leaping[slot]
            if req.steps == req.budget:
                h = self.leaps.pop(req.sid)
                if not h.done:
                    h.cancel()
                eng.release(req.sid)
                self._admit(slot, self.answer)
        return dt

    # -- window -----------------------------------------------------------------

    def window(self, seconds: float) -> dict:
        eng = self.engine
        s0, v0 = self.driver.stats.snapshot(), dataclasses.replace(eng.stats)
        self.keys, self.admissions, self.peak_pages = 0, 0, 0
        lat = []
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while time.perf_counter() < t_end:
            lat.append(self._step())
        jax.block_until_ready(self.driver.state)
        window_s = time.perf_counter() - t0
        s1, v1 = self.driver.stats.snapshot(), eng.stats
        committed = (s1.blocks_migrated - s0.blocks_migrated) + (s1.blocks_forced - s0.blocks_forced)
        decoded = v1.tokens_decoded - v0.tokens_decoded
        lat_ms = np.asarray(lat) * 1e3
        self.log(f"window {window_s:.6f} s: {len(lat)} decode steps, {self.admissions} "
                 f"admissions, {committed} pages committed "
                 f"({s1.blocks_forced - s0.blocks_forced} forced), "
                 f"{s1.dirty_rejections - s0.dirty_rejections} dirty rejections, "
                 f"{len(self.handles)} leaps so far, peak {self.peak_pages} pages held "
                 f"of {eng.n_pages}, decode p50 {np.percentile(lat_ms, 50):.3f} ms")
        return {
            "attempted": v1.decode_steps - v0.decode_steps,
            "e2e": {
                "migrate_gib_s": committed * self.page_bytes / window_s / GIB,
                "write_p95_ms": float(np.percentile(lat_ms, 95)),
            },
            "facts": {
                "ticks": s1.ticks - s0.ticks,
                "committed_blocks": committed,
                "block_bytes": self.page_bytes,
                "bytes_copied": s1.bytes_copied - s0.bytes_copied,
                "useful_bytes": committed * self.page_bytes,
                # every decoded token computes its logits; a prefill, its last one's
                "model_flops": serve_work.model_flops(
                    self.cfg, decoded + v1.tokens_prefilled - v0.tokens_prefilled,
                    decoded + self.admissions, self.keys),
                "kv_bytes_read": serve_work.paged_decode_bytes(
                    self.cfg, v1.kv_pages_read - v0.kv_pages_read, self.page,
                    jnp.dtype(self.cfg["torch_dtype"]).itemsize),
                "window_s": window_s,
            },
        }

    # -- check ------------------------------------------------------------------

    def check(self) -> dict:
        eng = self.engine
        drained = eng.drain()
        # every running sequence's stray pages home, then every leap resolved
        self.handles += eng.session.apply(eng, reroute=False)
        drained = eng.drain() and drained
        unbalanced = 0
        for h in self.handles:
            p = h.progress()
            unbalanced += not (h.done and p.committed + p.forced + p.cancelled == p.requested)
        misplaced = sum(
            int(np.count_nonzero(eng.facade.region_of(np.asarray(s.block_ids, np.int32))
                                 != s.region))
            for s in eng.seqs.values())
        mirror_bad = sum(not np.array_equal(host, np.asarray(dev)[ids])
                         for ids, host, dev in self.boundaries)
        mirror_bad += not np.array_equal(self.driver.host_table(),
                                         np.asarray(self.driver.state.table))
        tracked = [self.reqs[s] for s in self.tracked]
        leapt = sum(r.steps_leaping > 0 for r in tracked)
        errs = self._errors(tracked, self.dims)
        tol = self.cfg["tolerances"]
        checks = [(name, errs[name], tol[name]) for name in ERRORS] + [
            ("tracked_not_leapt", max(0, int(self.mix["min_tracked_leapt"]) - leapt), 0),
            ("undrained", 0 if drained else 1, 0),
            ("leaps_unbalanced", unbalanced, 0),
            ("pages_misplaced", misplaced, 0),
            ("mirror_mismatch", mirror_bad, 0),
        ]
        self.log(f"tracked slots {self.tracked.tolist()}: "
                 + ", ".join(f"{len(r.rows)} logits rows, {r.steps_leaping} steps leaping"
                             for r in tracked)
                 + f"; {len(self.boundaries)} leap boundaries")
        failed = sum(v > lim for _, v, lim in checks[: len(ERRORS)])
        return {"checks": checks, "failed": failed}

    def _errors(self, reqs, dims) -> dict:
        """The program's logits rows and KV pages of ``reqs`` against the
        reference's full forward pass over the tokens each has been fed,
        computed a layer at a time: the largest row error of each
        (``ERRORS``)."""
        eng = self.engine
        params = eng.params
        seqs = [eng.seqs[r.sid] for r in reqs]
        toks = [jnp.asarray(s.tokens[: s.length], jnp.int32) for s in seqs]
        pages = [self.driver.read(np.asarray(s.block_ids, np.int32), note=False) for s in seqs]
        xs = [ref.embed(params["embed"], t, dims) for t in toks]
        kv_err = []  # per layer
        for layer in range(self.model.n_layers):
            w = reference_weights(params, layer)
            kv_err.append(0.0)
            for i, s in enumerate(seqs):
                xs[i], k, v = ref.layer(xs[i], w, dims)
                got = jnp.moveaxis(pages[i][:, layer], 0, 1)  # [2, n_pages, BLK, W]
                got = got.reshape(2, -1, got.shape[-1])[:, : s.length]
                kv_err[-1] = max(kv_err[-1],
                                 float(row_rel_err(got[0], k.reshape(s.length, -1))),
                                 float(row_rel_err(got[1], v.reshape(s.length, -1))))
        final_norm = 1.0 + params["final_norm"].astype(F32)
        logits_err = 0.0
        for i, r in enumerate(reqs):
            pos = np.asarray([p for p, _ in r.rows], np.int32)
            want = take_rows(ref.head(xs[i], final_norm, params["embed"], dims), pos)
            got = jnp.asarray(np.stack([row for _, row in r.rows]))
            logits_err = max(logits_err, float(row_rel_err(got, want)))
        return {"logits_rel_err": logits_err, "kv_rel_err": max(kv_err),
                "kv_layer0_rel_err": kv_err[0]}
