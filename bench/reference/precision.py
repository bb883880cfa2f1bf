"""Matrix products of the plain references at a named precision.

``highest`` is a float32 product at full precision (six bfloat16 passes on
the TPU's MXU, exact float32 on a CPU).  ``high`` is the three-pass
bfloat16 product (``hi*hi + hi*lo + lo*hi`` of each operand split into a
bfloat16 high part and a bfloat16 remainder), written out so that it means
the same on every platform: it is the precision one step below the float32
that the configurations state, and the references run at it serve as the
control that the correctness check must refuse.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

PRECISIONS = ("highest", "high")


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def dot(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """``a @ b`` in float32 at ``precision``."""
    def one(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    if precision == "highest":
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if precision == "high":
        ah, al = _split(a)
        bh, bl = _split(b)
        return one(ah, bh) + (one(ah, bl) + one(al, bh))
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def np_dot(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """``a @ b`` on the host: float64 for ``float64``, otherwise float32 with
    the operands rounded as :func:`dot` rounds them."""
    if precision == "float64":
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    def bf(x):
        return x.astype(ml_dtypes.bfloat16).astype(np.float32)

    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if precision == "highest":
        return a @ b
    if precision == "high":
        ah, bh = bf(a), bf(b)
        al, bl = bf(a - ah), bf(b - bh)
        return ah @ bh + (ah @ bl + al @ bh)
    raise ValueError(f"unknown precision {precision!r}")
