"""The controls and the planted faults a cell's comparison must catch.

Each is a context manager that breaks the timed path underneath a run
and restores it on exit; ``tools/controls.py`` runs a cell through the
harness under one of them on the chip at the cell's own size, and
``tests/test_perfbench_checks.py`` at sizes a test run can hold.

* ``products_at(passes)``: every float32 product of the program's XLA
  code as one or three bfloat16 passes, written out so that a CPU computes
  what a TPU would (a CPU multiplies float32 whatever the precision says).
  Three passes are the step below the HIGHEST the configurations state;
  one pass is the step below that, and the graph cell's control.
* ``labels_altered()``: one answer in seven moved to the next cluster
  where the nearest-center assignment produces it.
* ``eigenvalues_altered()``: the eigensolver's eigenvalues moved by 1e-3.
* ``step_unchanged()``: every Lanczos step returns its state unchanged.
* ``half_rows()``: every operator product in the Lanczos steps leaves
  out the second half of the rows.
"""
from __future__ import annotations

import contextlib
import importlib

PRECISION_USERS = (
    "repro.cluster.estimator", "repro.core.chebdav", "repro.core.kmeans",
    "repro.core.lanczos", "repro.core.laplacian", "repro.core.similarity",
)
KERNEL_USERS = (
    "repro.kernels.block_matvec", "repro.kernels.fused_rbf_matmat",
    "repro.kernels.kmeans_assign", "repro.kernels.rbf_similarity",
)


def matmul_passes(a, b, passes: int):
    """``a @ b`` for float32 operands as ``passes`` bfloat16 products with
    float32 accumulation: 1 is ``a_hi b_hi``, 3 adds ``a_hi b_lo + a_lo b_hi``
    (what a TPU does for ``Precision.DEFAULT`` and ``Precision.HIGH``)."""
    import jax.numpy as jnp
    f32, bf16 = jnp.float32, jnp.bfloat16
    a, b = jnp.asarray(a, f32), jnp.asarray(b, f32)
    ah, bh = a.astype(bf16), b.astype(bf16)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=f32)

    out = dot(ah, bh)
    if passes == 3:
        al = (a - ah.astype(f32)).astype(bf16)
        bl = (b - bh.astype(f32)).astype(bf16)
        out = out + dot(ah, bl) + dot(al, bh)
    return out


@contextlib.contextmanager
def _patched(targets):
    """Set ``module.attr = value`` for each (module, attr, value)."""
    import jax
    saved = []
    for mod, attr, value in targets:
        m = importlib.import_module(mod)
        saved.append((m, attr, getattr(m, attr)))
        setattr(m, attr, value)
    jax.clear_caches()
    try:
        yield
    finally:
        for m, attr, value in reversed(saved):
            setattr(m, attr, value)
        jax.clear_caches()


def products_at(passes: int):
    """Every float32 product of the program's XLA code as ``passes``
    bfloat16 passes.  With one pass the Pallas kernels' float32 products
    drop to ``Precision.DEFAULT`` too (one pass on a TPU); with three they
    stay at HIGHEST, because Mosaic refuses ``Precision.HIGH``."""
    import jax
    import jax.numpy as jnp
    assert passes in (1, 3)

    def mxu(dtype):
        return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
                and passes == 3 else jax.lax.Precision.DEFAULT)

    def matmul(a, b):
        if jnp.result_type(a, b) == jnp.float32:
            return matmul_passes(a, b, passes)
        return jnp.matmul(a, b)

    return _patched([(m, "matmul", matmul) for m in PRECISION_USERS]
                    + [("repro.precision", "matmul", matmul)]
                    + [(m, "mxu_precision", mxu) for m in KERNEL_USERS]
                    + [("repro.precision", "mxu_precision", mxu)])


def labels_altered():
    from repro.core import kmeans as km
    original = km.assign

    def assign(y, centers):
        import jax.numpy as jnp
        labels = original(y, centers)
        bump = (jnp.arange(labels.shape[0]) % 7 == 0).astype(labels.dtype)
        return (labels + bump) % centers.shape[0]

    return _patched([("repro.core.kmeans", "assign", assign)])


def eigenvalues_altered():
    from repro.cluster import eigensolvers as es
    targets = []
    for name in ("lanczos", "block-lanczos"):
        fn = es.EIGENSOLVERS.get(name)

        def moved(est, op, key, _fn=fn):
            evals, Z, info = _fn(est, op, key)
            return evals + 1e-3, Z, info

        targets.append((name, moved))

    @contextlib.contextmanager
    def swap():
        saved = {n: es.EIGENSOLVERS.get(n) for n, _ in targets}
        try:
            for n, f in targets:
                es.EIGENSOLVERS._entries[n] = f
            yield
        finally:
            for n, f in saved.items():
                es.EIGENSOLVERS._entries[n] = f

    return swap()



def step_unchanged():
    def body(matmat, state):
        return state

    return _patched([("repro.core.lanczos", "_block_step_body", body)])


def half_rows():
    from repro.core import lanczos as lz
    original = lz._block_step_body

    def body(matmat, state):
        import jax.numpy as jnp

        def halved(V):
            W = matmat(V)
            keep = jnp.arange(W.shape[0]) < W.shape[0] // 2
            return jnp.where(keep[:, None], W, 0)

        return original(halved, state)

    return _patched([("repro.core.lanczos", "_block_step_body", body)])
