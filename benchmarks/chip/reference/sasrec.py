"""Plain reference of the SASRec user tower (Kang & McAuley,
arXiv:1808.09781), in jax.numpy and float32, with no kernel, cache or
batching logic of the system under test, which it does not import.

Each block is pre-LayerNorm: causal single- or multi-head self-attention
with an output projection, then a position-wise ReLU feed-forward of width
``embed_dim``, each added to its input. Item and position embeddings are
summed and zeroed at padded positions (id -1); a final LayerNorm gives the
user embedding at the last position. Departures from the paper, as the
system serves it: no dropout (inference), padded positions are zeroed at the
input only (the paper re-applies its timeline mask after each block), and
LayerNorm's epsilon is 1e-5.

Weights are the benchmark's own, drawn from the seed, in the parameter
layout the system's tower takes; the system and the reference read the same
arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30
LN_EPS = 1e-5


def init_params(key, tower: dict) -> dict:
    """Random weights from ``key``: N(0, 1/fan_in) projections, small
    random biases and LayerNorm gains near 1, so that every term of the
    block shows in the output."""
    D, S, V = tower["embed_dim"], tower["seq_len"], tower["vocab"]
    ks = iter(jax.random.split(key, 4 + 12 * tower["n_blocks"]))

    def nrm(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    blocks = []
    for _ in range(tower["n_blocks"]):
        blocks.append({
            "wq": nrm((D, D), D ** -0.5), "wk": nrm((D, D), D ** -0.5),
            "wv": nrm((D, D), D ** -0.5), "wo": nrm((D, D), D ** -0.5),
            "w1": nrm((D, D), D ** -0.5), "b1": nrm((D,), 0.1),
            "w2": nrm((D, D), D ** -0.5), "b2": nrm((D,), 0.1),
            "ln1_w": 1.0 + nrm((D,), 0.1), "ln1_b": nrm((D,), 0.1),
            "ln2_w": 1.0 + nrm((D,), 0.1), "ln2_b": nrm((D,), 0.1),
        })
    return {"item_emb": nrm((V, D), 0.01), "pos_emb": nrm((S, D), 0.01),
            "blocks": blocks,
            "ln_w": 1.0 + nrm((D,), 0.1), "ln_b": nrm((D,), 0.1)}


def _ln(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def forward(params: dict, feats: dict, tower: dict, precision) -> jnp.ndarray:
    """(B, seq_len) item ids (-1 = pad) -> (B, embed_dim) user embedding.
    Every matrix product runs at ``precision``: a ``jax.lax.Precision``, or
    ``"bf16_3x"`` for three bfloat16 passes on any platform (the control
    one step below float32)."""
    seq = feats["seq"]
    B, S = seq.shape
    D, H = tower["embed_dim"], tower["n_heads"]
    hd = D // H

    def ein(spec, a, b):
        if precision != "bf16_3x":
            return jnp.einsum(spec, a, b, precision=precision)
        # three bfloat16 passes, as a TPU's "high" precision: the product of
        # the high parts and both cross terms, float32 accumulation
        a_hi, b_hi = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        a_lo = (a - a_hi.astype(a.dtype)).astype(jnp.bfloat16)
        b_lo = (b - b_hi.astype(b.dtype)).astype(jnp.bfloat16)
        return sum(jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)
                   for x, y in ((a_hi, b_hi), (a_hi, b_lo), (a_lo, b_hi)))

    def mm(a, w):
        return ein("bsd,de->bse", a, w)

    x = params["item_emb"][jnp.maximum(seq, 0)] + params["pos_emb"][None, :S]
    x = jnp.where((seq >= 0)[..., None], x, 0.0)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    for p in params["blocks"]:
        h = _ln(x, p["ln1_w"], p["ln1_b"])
        q = mm(h, p["wq"]).reshape(B, S, H, hd)
        k = mm(h, p["wk"]).reshape(B, S, H, hd)
        v = mm(h, p["wv"]).reshape(B, S, H, hd)
        s = ein("bqhd,bkhd->bhqk", q, k)
        s = jnp.where(causal[None, None], s * hd ** -0.5, NEG_INF)
        a = jax.nn.softmax(s, axis=-1)
        o = ein("bhqk,bkhd->bqhd", a, v)
        x = x + mm(o.reshape(B, S, D), p["wo"])
        h2 = _ln(x, p["ln2_w"], p["ln2_b"])
        x = x + mm(jax.nn.relu(mm(h2, p["w1"]) + p["b1"]), p["w2"]) + p["b2"]
    return _ln(x, params["ln_w"], params["ln_b"])[:, -1]


def flops_per_inference(tower: dict) -> int:
    """Matrix-product FLOPs of one user-tower inference (2 per
    multiply-add): per block the four D x D projections and the D-wide
    feed-forward's two products over S positions, and the S x S score and
    mixing products."""
    D, S = tower["embed_dim"], tower["seq_len"]
    per_block = 2 * S * D * D * 4 + 2 * S * S * D * 2 + 2 * S * D * D * 2
    return tower["n_blocks"] * per_block
