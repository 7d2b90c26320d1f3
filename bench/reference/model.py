"""Plain float32 forward of the served decoder-only transformer.

Independent of ``src/``: straightforward ``jax.numpy`` in float32 at
``Precision.HIGHEST``, one sequence at a time, no cache, no kernels, no
batching.  It reads the dense family's weight tree
(``bench/families/dense.py``), which exports it as that family's
reference.

The equations are those the served program states: pre-norm residual
blocks, RMSNorm (``x * rsqrt(mean(x^2) + eps) * scale``), rotary embedding
on the whole head in the rotate-half form with ``theta^(-i/half)``
frequencies, grouped-query attention (query head ``h`` reads KV head
``h // (Hq / Hkv)``), causal softmax, a SwiGLU feed-forward
(``down(silu(gate x) * up x)``) and a head that is the embedding table
when tied.  Where a configuration's published model differs (stablelm-3b's
LayerNorm and 25% rotary), its file lists the departure; the reference
follows the served equations so that it and the program compute the same
function.

``weights="fp8"`` is the control: every matrix is rounded through
float8_e4m3fn with one scale per output channel, the next precision below
the bf16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def fp8_round(w, axis: int):
    """Round ``w`` through float8_e4m3fn, one scale per slice along all
    axes but ``axis`` (the reduced, input axis)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (S, H, D); rotate-half over the whole head."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-jnp.log(jnp.float32(theta)) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _f32(tree, weights: str):
    def leaf(path, a):
        a = a.astype(F32)
        name = path[-1].key
        if weights == "fp8" and name == "w":      # (in, out): scale per out
            a = fp8_round(a, axis=-2)
        if weights == "fp8" and name == "table":  # (V, d): scale per row
            a = fp8_round(a, axis=-1)
        return a

    return jax.tree_util.tree_map_with_path(leaf, tree)


def _layer(x, p, m, pos):
    S = x.shape[0]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    a = p["attn"]

    def lin(q, h):
        y = mm(h, q["w"])
        return y + q["b"] if "b" in q else y

    h = rmsnorm(x, p["attn_norm"]["scale"], eps)
    q = lin(a["wq"], h).reshape(S, hq, hd)
    k = lin(a["wk"], h).reshape(S, hkv, hd)
    v = lin(a["wv"], h).reshape(S, hkv, hd)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / jnp.sqrt(F32(hd))
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    x = x + mm(o.reshape(S, hq * hd), a["wo"]["w"])
    h = rmsnorm(x, p["ffn_norm"]["scale"], eps)
    f = p["ffn"]
    return x + mm(jax.nn.silu(mm(h, f["gate"]["w"])) * mm(h, f["up"]["w"]),
                  f["down"]["w"])


@partial(jax.jit, static_argnames=("mkey", "weights"))
def _hidden(params, tokens, *, mkey, weights):
    m = dict(mkey)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    emb = _f32(params["embedding"], weights)["table"]
    x = emb[tokens]

    def body(x, lp):
        return _layer(x, _f32(lp, weights), m, pos), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"].astype(F32), m["norm_eps"])


@partial(jax.jit, static_argnames=("weights",))
def _head(params, *, weights):
    head = params["lm_head"] if "lm_head" in params else params["embedding"]
    return _f32(head, weights)["table"]


def model_key(m: dict) -> tuple:
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "rope_theta", "norm_eps")
    return tuple((k, m[k]) for k in keys)


def hidden(params, tokens, m: dict, weights: str = "f32"):
    """Final-normed hidden states (S, d) of one sequence; causal, so
    padding after the last real token leaves earlier rows unchanged."""
    return _hidden(params, jnp.asarray(tokens, jnp.int32), mkey=model_key(m),
                   weights=weights)


def head(params, weights: str = "f32"):
    """The output head as a float32 (V, d) matrix: logits are h @ head.T."""
    return _head(params, weights=weights)
