"""Plain float32 reference of IBM Granite-3.0's forward pass.

Written from the Hugging Face ``GraniteForCausalLM`` equations
(https://huggingface.co/ibm-granite/granite-3.0-2b-base, ``model_type:
granite``) and independent of the model and serving code of this package:
no kernels, no cache, no batching, one sequence at a time, every matrix
product in float32 at ``highest`` precision::

    x       = embed[ids] * embedding_multiplier
    each layer:
      h     = rms_norm(x, input_norm)
      q,k,v = h @ wq, h @ wk, h @ wv            (RoPE on q and k)
      a     = softmax(attention_multiplier * q.k^T, causal) @ v   (GQA)
      x     = x + residual_multiplier * (a @ wo)
      h     = rms_norm(x, post_norm)
      x     = x + residual_multiplier * ((silu(h @ w_gate) * (h @ w_up)) @ w_down)
    logits  = (rms_norm(x, final_norm) @ embed^T) / logits_scaling

where ``rms_norm(x, w) = w * x / sqrt(mean(x^2) + rms_norm_eps)``.

Departures from the published model: the weights are random, and matrices
are stored ``[in, out]`` (``x @ w``) where the Hugging Face checkpoint keeps
``nn.Linear`` weights ``[out, in]``.

``weights`` is ``{"embed": [V, D], "final_norm": [D], "layers": [per-layer
dict of input_norm, wq, wk, wv, wo, post_norm, w_gate, w_up, w_down]}`` in
any float dtype; each is upcast to float32 where it is used, so a caller may
hand the layers over one at a time (:func:`embed`, :func:`layer`,
:func:`head`) to keep one layer in float32 at once.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    """The published configuration's numbers this reference reads."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    rms_norm_eps: float
    rope_theta: float
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_config(cls, config: dict) -> "Dims":
        """From a Hugging Face style ``config.json`` dict."""
        return cls(**{f.name: config[f.name] for f in dataclasses.fields(cls)})


def rms_norm(x, w, eps):
    return w.astype(F32) * (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps))


def rope(x, theta):
    """x: [S, heads, hd] at positions 0..S-1 (rotate-half convention)."""
    s, _, hd = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    freqs = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]  # [S, hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]  # [S, 1, hd]
    rotated = jnp.concatenate([-x[..., hd // 2 :], x[..., : hd // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


@partial(jax.jit, static_argnames=("dims",))
def embed(table, ids, dims: Dims):
    """Residual stream at the first layer: ``[S, D]`` float32."""
    return table[ids].astype(F32) * dims.embedding_multiplier


@partial(jax.jit, static_argnames=("dims",))
def layer(x, w, dims: Dims):
    """One decoder layer over the whole sequence ``x [S, D]``.

    Returns ``(x', k, v)``: the new residual stream, and the layer's keys
    (after RoPE) and values, ``[S, num_key_value_heads, head_dim]`` each.
    """
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        h_q, h_kv, hd = dims.num_attention_heads, dims.num_key_value_heads, dims.head_dim
        h = rms_norm(x, w["input_norm"], dims.rms_norm_eps)
        q = (h @ w["wq"].astype(F32)).reshape(s, h_q, hd)
        k = (h @ w["wk"].astype(F32)).reshape(s, h_kv, hd)
        v = (h @ w["wv"].astype(F32)).reshape(s, h_kv, hd)
        q, k = rope(q, dims.rope_theta), rope(k, dims.rope_theta)
        group = h_q // h_kv  # query head j reads key/value head j // group
        kr, vr = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, kr) * dims.attention_multiplier
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vr)
        x = x + dims.residual_multiplier * (a.reshape(s, h_q * hd) @ w["wo"].astype(F32))
        h = rms_norm(x, w["post_norm"], dims.rms_norm_eps)
        mlp = jax.nn.silu(h @ w["w_gate"].astype(F32)) * (h @ w["w_up"].astype(F32))
        x = x + dims.residual_multiplier * (mlp @ w["w_down"].astype(F32))
        return x, k, v


@partial(jax.jit, static_argnames=("dims",))
def head(x, final_norm, table, dims: Dims):
    """Logits ``[S, V]`` float32 of the tied head."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, final_norm, dims.rms_norm_eps)
        return (h @ table.astype(F32).T) / dims.logits_scaling


def forward(weights: dict, ids, dims: Dims):
    """Whole forward pass over one sequence ``ids [S]``.

    Returns ``(logits [S, V], ks, vs)`` with ``ks``/``vs`` stacked over the
    layers, ``[L, S, num_key_value_heads, head_dim]``, all float32.
    """
    x = embed(weights["embed"], jnp.asarray(ids), dims)
    ks, vs = [], []
    for w in weights["layers"]:
        x, k, v = layer(x, w, dims)
        ks.append(k)
        vs.append(v)
    return head(x, weights["final_norm"], weights["embed"], dims), jnp.stack(ks), jnp.stack(vs)
