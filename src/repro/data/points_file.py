"""Points on disk: an (n, d) ``.npy`` of rows, as users keep an
embeddings file.

bfloat16 rows are stored as ``ml_dtypes.bfloat16`` (JAX's ``jnp.bfloat16``
type).  ``np.load`` cannot name that dtype and returns the raw two-byte
records (``|V2``), which :func:`load_points` views as bfloat16 again.
Other float rows are read as float32.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

BF16 = np.dtype(jnp.bfloat16)


def load_points(path: str) -> np.ndarray:
    """The (n, d) rows of ``path``: bfloat16 as stored, else float32."""
    a = np.load(path)
    if a.dtype == np.dtype("V2"):
        a = a.view(BF16)
    if a.ndim != 2:
        raise ValueError(f"{path}: expected (n, d) rows, got shape "
                         f"{a.shape}")
    if a.dtype == BF16:
        return a
    if not np.issubdtype(a.dtype, np.floating):
        raise ValueError(f"{path}: expected float rows, got {a.dtype}")
    return a.astype(np.float32, copy=False)
