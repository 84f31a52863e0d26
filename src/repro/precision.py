"""Matrix-product precision: float32 products stay float32 on the TPU.

On a TPU, XLA and Mosaic multiply float32 operands in one bfloat16 pass
unless the product asks for more: about three significant digits, which
costs Lanczos its orthogonality and moves eigenvalues by 1e-3 (the fused
fit's ARI fell to 0.84).  So every product on the fit and predict paths
names its precision here, in the layer that multiplies: the rule then
holds however the product is reached (a caller's ``jit``, an engine
worker thread, the host-driven eigensolve).  The CPU multiplies float32
in float32 whatever the precision says, so CPU results do not change.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mxu_precision(dtype) -> jax.lax.Precision:
    """HIGHEST for float32 operands, the default for anything else:
    bfloat16 operands need no more, and the default is the only precision
    Mosaic takes for them."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else jax.lax.Precision.DEFAULT)


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at :func:`mxu_precision` of the operands' common dtype."""
    return jnp.matmul(a, b, precision=mxu_precision(jnp.result_type(a, b)))
