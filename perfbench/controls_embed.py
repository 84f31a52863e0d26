"""The controls of the embedding cell (``fit_points``): faults planted in
the fused kernel, each a context manager like those of
``perfbench/controls.py`` (whose ``half_rows``, ``labels_altered`` and
``products_at`` the cell uses too, and this module hands on).

* ``tile_bf16()``: the kernel's RBF tile times V product in one bfloat16
  pass, written out so that a CPU computes what a TPU would; the step
  below the float32 at HIGHEST the configuration states.
* ``tile_bf16_wide()``: the same, in the passes wider than one column
  only (the eigensolver's; the degree pass stays float32).
* ``gram_drops_last_dtile()``: the Gram tile leaves out the last feature
  tile of every grid cell (the squared norms stay whole).
"""
from __future__ import annotations

from perfbench.controls import (  # noqa: F401
    _patched, half_rows, labels_altered, products_at)

KERNEL = "repro.kernels.fused_rbf_matmat"


def tile_bf16(min_width: int = 1):
    import importlib

    import jax
    import jax.numpy as jnp
    original = importlib.import_module(KERNEL)._tile_times

    def tile_times(tile, w, dtype):
        if w.shape[1] < min_width:
            return original(tile, w, dtype)
        return jax.lax.dot_general(
            tile.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    return _patched([(KERNEL, "_tile_times", tile_times)])


def tile_bf16_wide():
    return tile_bf16(min_width=2)


def gram_drops_last_dtile():
    import importlib

    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    original = importlib.import_module(KERNEL)._gram_tile

    def gram_tile(x, y, dtype):
        last = pl.program_id(2) == pl.num_programs(2) - 1
        return jnp.where(last, 0.0, original(x, y, dtype))

    return _patched([(KERNEL, "_gram_tile", gram_tile)])
