"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(shape, dtype, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("n,m,d", [(64, 64, 4), (128, 96, 17), (200, 150, 33),
                                   (257, 129, 8), (512, 512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rbf_similarity(n, m, d, dtype):
    x = _rand((n, d), dtype, 0)
    y = _rand((m, d), dtype, 1)
    got = ops.rbf_similarity(x, y, 1.3, interpret=True)
    want = ref.rbf_similarity(x.astype(jnp.float32), y.astype(jnp.float32), 1.3)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol, rtol=tol)
    assert got.shape == (n, m)


@pytest.mark.parametrize("n,m", [(256, 512), (300, 700), (1024, 256), (65, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_matvec(n, m, dtype):
    A = _rand((n, m), dtype, 2)
    v = _rand((m,), dtype, 3)
    got = ops.block_matvec(A, v, interpret=True)
    want = ref.block_matvec(A.astype(jnp.float32), v.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol * np.abs(np.asarray(want)).max(), rtol=tol)


@pytest.mark.parametrize("n,m,b", [(256, 512, 8), (300, 700, 3),
                                   (1024, 256, 16), (65, 130, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_matmat(n, m, b, dtype):
    A = _rand((n, m), dtype, 6)
    V = _rand((m, b), dtype, 7)
    got = ops.block_matmat(A, V, interpret=True)
    want = ref.block_matmat(A.astype(jnp.float32), V.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol * np.abs(np.asarray(want)).max(),
                               rtol=tol)
    assert got.shape == (n, b)


def test_block_kernels_interpret_autodetect():
    """No hardcoded interpret default: off-TPU the wrappers auto-select
    interpret mode (and still match the oracle) without the caller
    passing anything."""
    from repro.kernels import block_matvec as raw
    assert raw.interpret_default() == (jax.default_backend() != "tpu")
    A = _rand((128, 128), jnp.float32, 8)
    v = _rand((128,), jnp.float32, 9)
    np.testing.assert_allclose(np.asarray(ops.block_matvec(A, v)),
                               np.asarray(ref.block_matvec(A, v)), atol=1e-4)
    V = _rand((128, 4), jnp.float32, 10)
    np.testing.assert_allclose(np.asarray(ops.block_matmat(A, V)),
                               np.asarray(ref.block_matmat(A, V)), atol=1e-4)


@pytest.mark.parametrize("dtype,precision", [
    (jnp.float32, jax.lax.Precision.HIGHEST),
    (jnp.bfloat16, jax.lax.Precision.DEFAULT)])
def test_mxu_precision_keeps_float32_products_float32(dtype, precision):
    """Kernels ask Mosaic for float32 products on float32 operands (its
    default is one bfloat16 pass) and for the default on bfloat16, the
    only precision Mosaic takes for those."""
    from repro.precision import mxu_precision
    assert mxu_precision(dtype) == precision


@pytest.mark.parametrize("n,d,k", [(512, 8, 7), (513, 16, 3), (1000, 4, 11),
                                   (64, 32, 2)])
def test_kmeans_assign(n, d, k):
    p = _rand((n, d), jnp.float32, 4)
    c = _rand((k, d), jnp.float32, 5)
    idx, dist = ops.kmeans_assign(p, c, interpret=True)
    ri, rd = ref.kmeans_assign(p, c)
    assert bool(jnp.all(idx == ri))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rd), atol=1e-4)


def test_kernels_match_core_pipeline_pieces():
    """The kernels compute exactly what core/similarity + core/kmeans use."""
    from repro.core.similarity import rbf_kernel
    x = _rand((96, 5), jnp.float32, 6)
    np.testing.assert_allclose(
        np.asarray(ops.rbf_similarity(x, x, 0.9, interpret=True)),
        np.asarray(rbf_kernel(x, x, 0.9)), atol=2e-5, rtol=2e-5)
