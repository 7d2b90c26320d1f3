"""Quantized linear application + whole-model quantization policy.

``quantized_matmul`` is the integration point used by ``models.layers.
linear``: it consumes the quantized-weight leaf dict and either

* dequantizes in-graph (XLA path — used by dry-runs so ``cost_analysis``
  sees the true int4/int8 byte traffic), or
* calls the Pallas LUT-dequant GEMM kernel (TPU path / interpret mode).

``quantize_model_params`` applies the paper's deployment policy: Q4 tile
quantization for attention & FFN projections, Q8_0 for FFN down-projections
(§7.1: "we apply the Q8_0 quantization scheme [to FFN down] to reduce
quantization errors"), embeddings / norms / small vectors left in fp.
"""
from __future__ import annotations

import re
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant import tile_quant as TQ

# toggled by ops layer / tests; default False so dry-runs lower pure XLA
_USE_PALLAS = False


def use_pallas_kernels(flag: bool) -> bool:
    """Route quantized matmuls through the LUT-dequant GEMM kernel; returns
    the previous setting.  Read while tracing, so set it before an engine
    first runs."""
    global _USE_PALLAS
    prev, _USE_PALLAS = _USE_PALLAS, flag
    return prev


def is_quantized(leaf) -> bool:
    return isinstance(leaf, dict) and "codes" in leaf


def quantized_matmul(x: jnp.ndarray, qw: dict, group_size: int = 32) -> jnp.ndarray:
    """x: (..., K) @ dequant(qw) (K, N) -> (..., N)."""
    if _USE_PALLAS and "codebook" in qw:
        from repro.kernels import ops

        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y = ops.lut_dequant_matmul(x2, qw, group_size=group_size)
        return y.reshape(*lead, y.shape[-1])
    if "codebook" in qw:
        w = TQ.dequantize(qw, dtype=x.dtype, group_size=group_size)
    else:
        w = TQ.dequantize_q8(qw, dtype=x.dtype, group_size=group_size)
    return jnp.einsum("...d,df->...f", x, w)


# ---------------------------------------------------------------------------
# Model-level quantization
# ---------------------------------------------------------------------------

# path regex -> scheme name ("q4" | "q8" | None). First match wins.
DEFAULT_POLICY = [
    (r".*(down|fc2)/w$", "q8"),            # FFN down: Q8_0 (paper §7.1)
    (r".*(gate|up|fc1)/w$", "q4"),
    (r".*w[qkvo]/w$", "q4"),
    (r".*in_proj/w$", "q4"),
    (r".*out_proj/w$", "q4"),
    (r".*experts/down$", "q8"),
    (r".*experts/(gate|up)$", "q4"),
    (r".*", None),                          # embeddings, norms, etc.
]


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def quantize_model_params(params, *, scheme: str = "tile", codebook: str = "q4_0",
                          group_size: int = 32, policy=None):
    """Quantize eligible 2-D weights in a parameter pytree.

    Returns a new pytree in which quantized leaves are dicts
    {"codes", "scales"[, "codebook"]}.  Stacked (scanned) layer weights of
    shape (L, K, N) are quantized one layer at a time (``lax.map``): the
    nearest-code search holds a (K, N, 16) temporary, which for all 28
    layers of a 1.5B model at once would not fit one chip.
    """
    policy = policy or DEFAULT_POLICY

    def decide(path):
        for pat, sch in policy:
            if re.match(pat, path):
                return sch
        return None

    def q4(w):
        return TQ.quantize(w, scheme=scheme, codebook=codebook,
                           group_size=group_size)

    def q8(w):
        return TQ.quantize_q8(w, group_size=group_size)

    def one(path, leaf):
        ps = _path_str(path)
        sch = decide(ps)
        if sch is None or leaf.ndim not in (2, 3, 4):
            return leaf
        fn = q4 if sch == "q4" else q8
        for _ in range(leaf.ndim - 2):  # stacked layer and/or expert dims
            fn = partial(jax.lax.map, fn)
        # NB: the codebook is broadcast across stacked dims so that
        # lax.scan over stacked layer params can slice it uniformly.
        return fn(leaf)

    return jax.tree_util.tree_map_with_path(one, params)


def dequantize_model_params(params, group_size: int = 32):
    """Inverse of quantize_model_params (for accuracy baselines)."""

    def one(leaf):
        if not is_quantized(leaf):
            return leaf
        nstack = leaf["codes"].ndim - 2
        if "codebook" in leaf:
            fn = lambda c, s, cb: TQ.dequantize(
                {"codes": c, "scales": s, "codebook": cb}, group_size=group_size)
            for _ in range(nstack):
                fn = jax.vmap(fn)
            return fn(leaf["codes"], leaf["scales"], leaf["codebook"])
        fn = lambda c, s: TQ.dequantize_q8({"codes": c, "scales": s},
                                           group_size=group_size)
        for _ in range(nstack):
            fn = jax.vmap(fn)
        return fn(leaf["codes"], leaf["scales"])

    return jax.tree.map(one, params, is_leaf=is_quantized)
