"""Backend resolution and in-kernel helpers shared by the Pallas kernels.

Every kernel takes ``interpret: bool | None = None``; ``None`` follows the
backend at call time (:func:`resolve_interpret`): the Pallas interpreter on
CPU, Mosaic on TPU.

The helpers below are the forms Mosaic accepts on TPU v5e for the pieces
of the §5.2 dequant story that a plain ``jnp`` port gets refused for:

* fp16 vectors cannot be loaded into VMEM at all, so fp16 scale arrays
  ride in as bf16-typed raw bit patterns (:func:`as_bf16_bits`, a bitcast
  in XLA) and are decoded exactly in-kernel (:func:`f16_bits_to_f32`);
* only in-vreg 2-D gathers exist, so the 16-entry codebook lookup is a
  select chain (:func:`codebook_lookup`);
* uint8 vectors support neither shifts nor float casts, so packed codes
  widen to int32 before unpacking (:func:`unpack_codes`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` itself when given; for ``None``, True on CPU (Pallas
    interpreter) and False on TPU (Mosaic compile).

    Raises on any other backend: the kernels are written for one of the
    two, and silently interpreting on an accelerator would time Python."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run interpreted on 'cpu' or compiled "
                       f"on 'tpu'; the default backend is {backend!r}")


def require_interpret(interpret: bool, what: str, why: str) -> None:
    """Raise for a compiled (TPU) call of a kernel Mosaic refuses."""
    if not interpret:
        raise NotImplementedError(
            f"{what} runs only in the Pallas interpreter: {why}")


def as_bf16_bits(x: jnp.ndarray) -> jnp.ndarray:
    """fp16 array -> the same bits typed bf16 (a free XLA bitcast): the
    form in which fp16 scales enter a kernel."""
    return jax.lax.bitcast_convert_type(x, jnp.bfloat16)


def f16_bits_to_f32(x: jnp.ndarray) -> jnp.ndarray:
    """bf16-typed vector holding fp16 bit patterns -> the exact f32 values
    (normals, subnormals, zeros, infs and NaNs alike)."""
    h = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
    sign = (h >> 15) << 31
    e = (h >> 10) & 0x1F
    m = h & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        sign | ((e + 112) << 23) | (m << 13), jnp.float32)
    special = jax.lax.bitcast_convert_type(
        sign | (0xFF << 23) | (m << 13), jnp.float32)
    sub = m.astype(jnp.float32) * (2.0 ** -24)
    sub = jnp.where(sign != 0, -sub, sub)
    return jnp.where(e == 0, sub, jnp.where(e == 31, special, normal))


def unpack_codes(codes: jnp.ndarray, *, packed: bool) -> jnp.ndarray:
    """(R, C) int8/uint8 codes -> (R, C) or, for two-per-byte packed q4
    codes (low nibble = even column), (R, 2C) int32."""
    c = codes.astype(jnp.int32)
    if not packed:
        return c
    return jnp.stack([c & 0xF, c >> 4], axis=-1).reshape(
        c.shape[0], 2 * c.shape[1])


def codebook_lookup(cb: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """vlut16 analogue: ``cb[idx]`` for a (1, 16) f32 codebook and int32
    indices in [0, 16), as a select chain."""
    vals = jnp.zeros(idx.shape, jnp.float32)
    for e in range(cb.shape[-1]):
        vals = jnp.where(idx == e, cb[:, e:e + 1], vals)
    return vals
