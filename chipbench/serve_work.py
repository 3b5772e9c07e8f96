"""Operations and bytes of serving a dense GQA decoder, from its published sizes.

``c`` is the configuration's ``config.json``-style dict (``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``num_attention_heads``,
``num_key_value_heads``, ``vocab_size``).  Model operations count each
multiply-add as 2: the matrix products a token needs (tied head included
where its logits are computed) and its attention over the tokens before it
and itself.  Work the program does beyond the model's, such as masked-out
scores or padded lanes, is not counted.
"""

from __future__ import annotations


def _sizes(c: dict):
    d, heads, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    return d, heads, kv, d // heads, c["intermediate_size"], c["num_hidden_layers"]


def layer_params(c: dict) -> int:
    """Matrix parameters of one layer: q, k, v, o and the SwiGLU's three."""
    d, heads, kv, hd, ff, _ = _sizes(c)
    return d * heads * hd + 2 * d * kv * hd + heads * hd * d + 3 * d * ff


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def attention_flops(c: dict, keys: int) -> int:
    """Scores and weighted values of one token over ``keys`` keys, all layers."""
    _, heads, _, hd, _, layers = _sizes(c)
    return 4 * heads * hd * keys * layers


def model_flops(c: dict, tokens: int, logits_rows: int, keys: int) -> int:
    """``tokens`` tokens through every layer's matrix products,
    ``logits_rows`` of them through the tied head, attending over ``keys``
    keys in all (each token over the tokens before it and itself: a decode
    step's token over its whole context, an ``n``-token prefill over
    ``n (n + 1) / 2``)."""
    return (2 * (int(tokens) * c["num_hidden_layers"] * layer_params(c)
                 + int(logits_rows) * head_params(c))
            + attention_flops(c, int(keys)))


def paged_decode_bytes(c: dict, pages_read: int, page_tokens: int, dtype_bytes: int) -> int:
    """HBM bytes the paged-attention kernel must read: for each page read, in
    every layer, its ``[2, page_tokens, kv_heads * head_dim]`` K/V slab.  The
    least bytes of the queries and outputs, under 1% of it at Granite's
    widths and the cell's contexts, are left out."""
    _, _, kv, hd, _, layers = _sizes(c)
    return int(pages_read) * layers * 2 * page_tokens * kv * hd * dtype_bytes
