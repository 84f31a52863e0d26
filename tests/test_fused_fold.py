"""The fused degree pass carries the first block-Lanczos step.

One pass of width b + 1 over ``[1 | R]`` gives the degrees and the
start pair ``(Q0, A Q0)``, so a ``fused-rbf`` + block-Lanczos fit makes
one fused pass fewer than one degree pass and ``steps`` block steps:

* the start pair against the operator's own pass, with padding rows, in
  bf16 and f32 rows, on one device and on four;
* a folded fit against the ``eigh`` oracle, and its pass counters;
* the solvers that take no start block (``chebdav``, ``eigh``) and the
  engine's fused route keep their width-1 degree pass;
* a second ``spectral_job --points`` of the same shapes compiles
  nothing.
"""
import inspect

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.cluster import SpectralClustering, ari
from repro.cluster.affinity import build_fused_rbf_operator
from repro.data import synthetic
from repro.distrib import mesh_utils


def unit_rows(n, d, seed):
    x = np.random.default_rng(seed).standard_normal((n, d))
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def fused_counts():
    return {k: m["value"] for k, m in obs.snapshot().items()
            if k.startswith("fused.passes") or k == "lanczos.start_folded"}


def pair_gap(x, b):
    """(|A Q0 - W0| / |A Q0|, |Q0^T Q0 - I|, |Q0| on padding, the
    degrees' widest relative gap from the width-1 pass's, n_pad)."""
    mesh = mesh_utils.local_mesh("rows")
    op = build_fused_rbf_operator(x, 1.0, mesh,
                                  start=(jax.random.PRNGKey(3), b))
    plain = build_fused_rbf_operator(x, 1.0, mesh)
    Q0, W0 = (np.asarray(a, np.float64) for a in op.start_block)
    W = np.asarray(op.matmat(jnp.asarray(Q0, jnp.float32)), np.float64)
    n = x.shape[0]
    deg = np.asarray(op.inv_sqrt, np.float64)[:n] ** -2
    deg1 = np.asarray(plain.inv_sqrt, np.float64)[:n] ** -2
    return (float(np.abs(W - W0).max() / np.abs(W).max()),
            float(np.abs(Q0.T @ Q0 - np.eye(b)).max()),
            float(np.abs(Q0[n:]).max()),
            float(np.max(np.abs(deg - deg1) / deg1)), op.n_pad)


@pytest.mark.parametrize("rows", ["bfloat16", "float32"])
@pytest.mark.parametrize("b", [1, 8])
def test_start_pair_is_the_operators_pass(rows, b):
    """n=200 rows pad to 256: W0 is A Q0 to float32 rounding, Q0 is
    orthonormal and zero on the padding, and the degrees are those of the
    width-1 degree pass."""
    x = jnp.asarray(unit_rows(200, 48, 0), jnp.dtype(rows))
    gap, orth, pad, deg, n_pad = pair_gap(x, b)
    assert n_pad == 256
    assert gap < 2e-6
    assert orth < 1e-5
    assert pad == 0.0
    assert deg < 1e-6


def test_start_pair_on_four_devices(subproc):
    """The same on a mesh of four: the width-(b + 1) pass goes through the
    row-sharded pass like any other width."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.cluster.affinity import build_fused_rbf_operator
from repro.distrib import mesh_utils
""" + inspect.getsource(pair_gap) + """
x = np.random.default_rng(0).standard_normal((200, 48))
x = jnp.asarray(x / np.linalg.norm(x, axis=1, keepdims=True), jnp.bfloat16)
assert len(jax.devices()) == 4
print("GAP", *pair_gap(x, 8))
""", 4)
    line = next(l for l in out.splitlines() if l.startswith("GAP"))
    gap, orth, pad, deg, n_pad = (float(v) for v in line.split()[1:])
    assert n_pad == 512
    assert gap < 2e-6
    assert orth < 1e-5
    assert pad == 0.0
    assert deg < 1e-6


@pytest.mark.parametrize("solver,b", [("block-lanczos", 4), ("lanczos", 1)])
def test_folded_fit_matches_eigh_and_counts_its_passes(solver, b):
    """A folded fit reaches the oracle's eigenvalues and the planted
    labels; its degree pass has width b + 1 and the loop one step fewer."""
    pts, truth = synthetic.blobs(300, 3, seed=7)
    x = jnp.asarray(pts)
    kw = dict(affinity="fused-rbf", sigma=1.0, seed=0)
    obs.reset()
    est = SpectralClustering(3, eigensolver=solver, block_size=b,
                             lanczos_steps=24, **kw).fit(x)
    steps = est.info_["block_steps"]
    assert steps == 24 // b
    assert fused_counts() == {f"fused.passes{{width={b + 1}}}": 1,
                              f"fused.passes{{width={b}}}": steps - 1,
                              "lanczos.start_folded": 1}
    assert est.info_["matrix_passes"] == steps - 1
    assert est.info_["engine"]["matrix_passes"] == steps
    oracle = SpectralClustering(3, eigensolver="eigh", **kw).fit(x)
    np.testing.assert_allclose(np.asarray(est.eigenvalues_),
                               np.asarray(oracle.eigenvalues_), atol=1e-4)
    assert ari(truth, np.asarray(est.labels_)) == 1.0


@pytest.mark.parametrize("solver", ["chebdav", "eigh"])
def test_solvers_without_a_start_block_keep_the_degree_pass(solver):
    pts, truth = synthetic.blobs(120, 3, seed=2)
    obs.reset()
    est = SpectralClustering(3, affinity="fused-rbf", eigensolver=solver,
                             sigma=1.0, seed=0).fit(jnp.asarray(pts))
    counts = fused_counts()
    assert counts["fused.passes{width=1}"] == 1
    assert "lanczos.start_folded" not in counts
    assert ari(truth, np.asarray(est.labels_)) == 1.0


def test_engine_fused_route_keeps_the_degree_pass():
    """``engine.run_job``'s fused route builds its operator without a
    start request: a width-1 degree pass, then its block steps."""
    from repro import engine
    from repro.data.chunked import BlobChunks
    reader = BlobChunks(256, 3, chunk_size=128, dim=4, spread=0.8, seed=0)
    plan = engine.JobPlan(n=256, chunk_size=128, k=3, sigma=1.0, seed=0,
                          path="fused", lanczos_steps=32, kmeans_rounds=10)
    obs.reset()
    res = engine.run_job(plan, reader)
    b, steps = plan.eff_block_size(), plan.num_block_steps()
    assert fused_counts() == {"fused.passes{width=1}": 1,
                              f"fused.passes{{width={b}}}": steps}
    assert res.stats["path"] == "fused"


def test_second_points_job_compiles_nothing(tmp_path):
    """The folded start's programs (the width-(b + 1) pass, the start QR
    and product, the folded first step) compile once per process: a
    second ``--points`` job of the same shapes traces, lowers and loads
    no program, and gives the same answers."""
    from repro.launch import spectral_job
    x = unit_rows(300, 64, 4).astype(ml_dtypes.bfloat16)
    path = tmp_path / "emb.npy"
    np.save(path, x)
    argv = ["--points", str(path), "--k", "3", "--affinity", "fused-rbf",
            "--eigensolver", "block-lanczos", "--block-size", "8",
            "--lanczos-steps", "24"]
    names = ("job", "fit.affinity", "fit.affinity.degree",
             "fit.eigensolve.krylov", "fit.assign.seed", "fit.assign.lloyd")

    def compiled(name):
        return {s.name: s for s in obs.spans()}[name].attrs.get(
            "jit_programs", 0)

    obs.reset()
    first = spectral_job.main(argv)
    assert compiled("fit.affinity.degree") > 0     # the counter is live
    obs.reset()
    second = spectral_job.main(argv)
    for name in names:
        assert compiled(name) == 0, name
    assert fused_counts()["lanczos.start_folded"] == 1
    for got, want in ((second.eigenvalues_, first.eigenvalues_),
                      (second._eigvecs, first._eigvecs),
                      (second.labels_, first.labels_)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
