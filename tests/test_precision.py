"""Float32 products on the fit and predict paths ask for float32.

On a TPU a float32 product with no precision runs as one bfloat16 pass,
and the CPU never shows the difference.  So the checks here read the
programs, not the numbers: every ``dot_general`` the fit-path code lowers
carries ``precision = [HIGHEST, HIGHEST]``, and no module of the fit path
multiplies with a bare ``@`` or a ``jnp`` product that names no precision.
"""
import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kmeans as km, laplacian as lp, lanczos as lz
from repro.core import similarity as sim
from repro.distrib import mesh_utils

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
# float64 NumPy code: the reference and the engine's host-side k-means
NUMPY_MODULES = {"cluster/reference.py", "engine/kmeans.py"}
_JNP_PRODUCTS = {"dot", "matmul", "einsum", "tensordot", "inner", "vdot"}


def _unnamed_products(path: pathlib.Path) -> list[str]:
    out = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
            out.append(f"{node.lineno}: @")
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and isinstance(node.func.value, ast.Name)
              and (node.func.value.id, node.func.attr) in (
                  {("jnp", p) for p in _JNP_PRODUCTS}
                  | {("lax", "dot_general"), ("lax", "dot")})
              and not any(k.arg == "precision" for k in node.keywords)):
            out.append(f"{node.lineno}: {node.func.value.id}."
                       f"{node.func.attr}")
    return out


@pytest.mark.parametrize("package", ["core", "cluster", "engine", "launch"])
def test_fit_path_products_name_their_precision(package):
    bad = {}
    for path in sorted((SRC / package).glob("*.py")):
        rel = path.relative_to(SRC).as_posix()
        if rel not in NUMPY_MODULES and _unnamed_products(path):
            bad[rel] = _unnamed_products(path)
    assert not bad, ("products without a precision (use "
                     f"repro.precision.matmul): {bad}")


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


def _block_state(n=64, steps=3, b=4):
    return lz.init_block_state(n, steps, jax.random.PRNGKey(0), b)


def _triangular_matmat():
    mesh = mesh_utils.local_mesh("rows", n_devices=1)
    upper = sim.similarity_upper_blocks(_rand((40, 3), 0), 1.0, mesh)
    n_pad = int(upper.diag.shape[0])
    return (lambda V: sim.sym_matmat(upper, V)), (_rand((n_pad, 4), 1),)


def _cases():
    S = jnp.abs(_rand((48, 48), 2))
    S = S + S.T
    valid = jnp.ones((48,), jnp.float32)
    y = _rand((64, 4), 3)
    state = km.KMeansState(it=jnp.zeros((), jnp.int32),
                           centers=_rand((3, 4), 4),
                           shift=jnp.asarray(1.0, jnp.float32))
    return {
        "pairwise_sq_dists": (sim.pairwise_sq_dists, (y, _rand((3, 4), 5))),
        "dense_operator": (
            lambda S, V: lp.make_dense_operator(S, valid)[0](V),
            (S, _rand((48, 4), 6))),
        "dense_shifted_matmat": (
            lambda S, V: lp.make_dense_shifted_matmat(S)(V),
            (S, _rand((48, 4), 6))),
        "triangular_matmat": _triangular_matmat(),
        # the per-step program engine.run_job's ooc eigensolve dispatches
        "run_job_block_lanczos_step": (
            lz._block_step_advance, (_block_state(), _rand((64, 4), 7))),
        # the Ritz vectors of block_ritz_pairs, at b = 4 and b = 1
        "block_ritz_pairs": (
            lz._ritz_vectors, (_block_state().V, _rand((12, 12), 8))),
        "lanczos_ritz_pairs": (
            lz._ritz_vectors,
            (_block_state(steps=5, b=1).V, _rand((5, 5), 9))),
        "lloyd_step": (
            lambda y, s: km.lloyd_step(y, jnp.ones((64,), jnp.float32), s),
            (y, state)),
        "minibatch_kmeans": (
            lambda y: km.minibatch_kmeans(
                y, jnp.ones((64,), jnp.float32), 3, jax.random.PRNGKey(2),
                iters=2, batch=16),
            (y,)),
    }


@pytest.mark.parametrize("case", sorted(_cases()))
def test_lowered_fit_path_products_are_float32(case):
    fn, args = _cases()[case]
    text = jax.jit(fn).lower(*args).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots, f"{case}: no product lowered"
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots), \
        [line for line in dots if "HIGHEST" not in line]
