"""Clustering points of real embedding widths through the fused affinity.

* the d-tiled fused kernel against its ``kernels/ref.py`` twin, for bf16
  and f32 rows over three feature tiles and a ragged tail;
* the schedule layer's feature tile: the default rule, the VMEM model at
  d=4,096, and the tile's legality;
* the points file: bf16 rows written with ``ml_dtypes`` read back as bf16;
* ``spectral_job --points`` against the float64 reference, with its
  spans and pass counters, and its refusal of a Krylov space below k;
* the fused operator as data for the compiled Lanczos loop: no host
  callback in it, and its passes counted on the host.
"""
import json

import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.cluster import SpectralClustering, ari
from repro.cluster.reference import spectral_reference
from repro.data import load_points
from repro.kernels import fused_rbf_matmat as frm, ops, ref
from repro.tune import Schedule, ScheduleError
from repro.tune.schedule import VMEM_BYTES, resolve, spec


def unit_rows(n, d, seed, dtype=jnp.float32):
    x = np.random.default_rng(seed).standard_normal((n, d))
    return jnp.asarray(x / np.linalg.norm(x, axis=1, keepdims=True),
                       jnp.float32).astype(dtype)


def topics(n, d, k, seed):
    """Unit rows around k topic directions over one shared direction
    (mean cosine about 0.6 within a topic, 0.4 between), and the topic of
    each row."""
    rng = np.random.default_rng(seed)
    lab = rng.integers(0, k, n)
    u0 = rng.standard_normal(d)
    mu = rng.standard_normal((k, d))
    x = (np.sqrt(0.4) * u0 / np.linalg.norm(u0)
         + np.sqrt(0.2) * (mu / np.linalg.norm(mu, axis=1,
                                               keepdims=True))[lab]
         + np.sqrt(0.4) * rng.standard_normal((n, d)) / np.sqrt(d))
    return x / np.linalg.norm(x, axis=1, keepdims=True), lab


# ---------------------------------------------------------------------------
# the d-tiled kernel


@pytest.mark.parametrize("b", [1, 8, 64])
@pytest.mark.parametrize("rows", ["bfloat16", "float32"])
@pytest.mark.parametrize("acc", ["inplace", "scratch"])
def test_d_tiled_kernel_matches_reference(rows, b, acc):
    """Feature tiles of 128 over d = 3 * 128 + 40 (the tail padded with
    zero columns), uneven n: the Gram tiles summed over the feature steps
    give the materialized product of the same rows."""
    n, d = 200, 3 * 128 + 40
    x = unit_rows(n, d, 0, jnp.dtype(rows))
    V = jnp.asarray(np.random.default_rng(1).standard_normal((n, b)),
                    jnp.float32)
    rs = jnp.asarray(np.random.default_rng(2).random(n), jnp.float32)
    sched = Schedule(bm=64, bn=128, bd=128, acc=acc, interpret=True)
    got = np.asarray(ops.fused_rbf_matmat(x, x, V, 0.8, rs, rs,
                                          schedule=sched))
    want = np.asarray(ref.fused_rbf_matmat(x, x, V, 0.8, rs, rs))
    assert got.shape == (n, b)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * np.abs(want).max())


def test_bf16_rows_keep_the_tile_product_f32():
    """bf16 rows: the Gram is exact and the tile x V product f32, so the
    result matches the f32 reference of the same rows far inside what a
    bf16 tile (about 2e-3 relative) would give; compute_dtype is not
    read."""
    x = unit_rows(256, 256, 3, jnp.bfloat16)
    V = jnp.asarray(np.random.default_rng(4).standard_normal((256, 8)),
                    jnp.float32)
    want = np.asarray(ref.fused_rbf_matmat(x, x, V, 1.0, jnp.ones(256),
                                           jnp.ones(256)))
    for cd in (None, "bf16"):
        got = np.asarray(ops.fused_rbf_matmat(x, x, V, 1.0, bm=128, bn=128,
                                              compute_dtype=cd,
                                              interpret=True))
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=1e-5 * np.abs(want).max())


# ---------------------------------------------------------------------------
# the schedule layer's feature tile


def test_default_feature_tile_rule():
    assert frm.default_d_tile(8) == 8
    assert frm.default_d_tile(4096, 2) == 1024
    assert frm.default_d_tile(4096, 4) == 512
    assert frm.default_d_tile(600, 4) == 384       # 2 tiles, 168 padded
    assert frm.default_tile(65536, 4096, 2) == 512
    assert frm.default_tile(65536, 8) == 256


@pytest.mark.parametrize("itemsize", [2, 4])
def test_resolve_at_width_4096_fits_vmem(itemsize):
    shape = dict(n=65536, m=65536, d=4096, b=64, itemsize=itemsize)
    for tile in (None, frm.default_tile(65536, 4096, itemsize)):
        s, _ = resolve("fused_rbf_matmat", None, bm=tile, bn=tile,
                       interpret=False, **shape)
        assert s.bd == frm.default_d_tile(4096, itemsize)
        assert spec("fused_rbf_matmat").vmem_model(s, **shape) <= VMEM_BYTES
    # the whole row at 512-row tiles would not fit
    with pytest.raises(ScheduleError, match="VMEM"):
        resolve("fused_rbf_matmat", {"bd": 4096}, bm=512, bn=512,
                interpret=False, **shape)


def test_vmem_model_counts_double_buffers():
    s = Schedule(bm=512, bn=512, bd=1024)
    one = (2 * 512 * 1024 * 2 + (512 * 64 + 4 * 512) * 4, 512 * 64 * 4)
    gram_and_tile = 2 * 512 * 512 * 4
    assert spec("fused_rbf_matmat").vmem_model(
        s, n=65536, m=65536, d=4096, b=64, itemsize=2) \
        == 2 * sum(one) + gram_and_tile


def test_feature_tile_legality():
    with pytest.raises(ScheduleError, match="multiple of 128"):
        spec("fused_rbf_matmat").check(
            Schedule(bm=128, bn=128, bd=200, interpret=False),
            n=4096, m=4096, d=4096)
    with pytest.raises(ScheduleError, match="no feature tile"):
        spec("block_matmat").check(Schedule(bm=8, bn=8, bd=8,
                                            interpret=True))
    # a whole row is one legal tile at any width
    spec("fused_rbf_matmat").check(Schedule(bm=128, bn=128, bd=200,
                                            interpret=False),
                                   n=4096, m=4096, d=200)


# ---------------------------------------------------------------------------
# the points file and the estimator


def test_load_points_reads_bf16_rows(tmp_path):
    x = np.asarray(unit_rows(16, 8, 5)).astype(ml_dtypes.bfloat16)
    np.save(tmp_path / "bf16.npy", x)
    assert np.load(tmp_path / "bf16.npy").dtype == np.dtype("V2")
    got = load_points(str(tmp_path / "bf16.npy"))
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(got.astype(np.float32),
                                  x.astype(np.float32))
    np.save(tmp_path / "f64.npy", x.astype(np.float64))
    assert load_points(str(tmp_path / "f64.npy")).dtype == np.float32
    np.save(tmp_path / "flat.npy", np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="rows"):
        load_points(str(tmp_path / "flat.npy"))


def test_estimator_keeps_bf16_rows_on_the_device():
    x, lab = topics(256, 512, 3, 6)
    xb = jnp.asarray(x, jnp.bfloat16)
    est = SpectralClustering(3, affinity="fused-rbf", seed=0,
                             lanczos_steps=32).fit(xb)
    assert est._train_x.dtype == jnp.bfloat16
    assert est.info_["engine"]["row_dtype"] == "bfloat16"
    assert ari(lab, np.asarray(est.labels_)) == 1.0


# ---------------------------------------------------------------------------
# spectral_job --points


def test_spectral_job_points_matches_reference(tmp_path, capsys):
    """n=1,024 rows of width 4,096 (four feature tiles) in bf16, k=8:
    the eigenvalues agree with the float64 reference's dense ``eigh`` to
    1e-5 (float32 against float64 on an operator of norm 1), and the
    labels recover the planted topics exactly."""
    from repro.launch import spectral_job
    n, d, k = 1024, 4096, 8
    x, lab = topics(n, d, k, 7)
    xb = x.astype(ml_dtypes.bfloat16)
    path = tmp_path / "emb.npy"
    np.save(path, xb)
    obs.reset()
    est = spectral_job.main([
        "--points", str(path), "--k", str(k), "--affinity", "fused-rbf",
        "--eigensolver", "block-lanczos", "--block-size", "16",
        "--lanczos-steps", "64", "--metrics-out",
        str(tmp_path / "m.json"), "--trace-out", str(tmp_path / "t.json")])
    assert "rows=bfloat16" in capsys.readouterr().out
    labels, evals = spectral_reference(xb.astype(np.float64), k,
                                       float(est.sigma_))
    np.testing.assert_allclose(np.asarray(est.eigenvalues_), evals,
                               rtol=0, atol=1e-5)
    assert ari(lab, np.asarray(est.labels_)) == 1.0
    assert ari(labels, np.asarray(est.labels_)) == 1.0
    metrics = json.loads((tmp_path / "m.json").read_text())
    # the degree pass carries the first block step: width 16 + 1
    assert metrics["fused.passes{width=17}"]["value"] == 1
    assert metrics["fused.passes{width=16}"]["value"] == 3
    assert "fused.passes{width=1}" not in metrics
    assert metrics["lanczos.start_folded"]["value"] == 1
    assert metrics["fused.d_tiles"]["value"] == 4
    names = {e["name"] for e in json.loads(
        (tmp_path / "t.json").read_text())["traceEvents"]}
    assert {"job.load", "job.to_device", "fit.affinity.degree"} <= names


def test_fused_lanczos_loop_holds_no_host_callback():
    """The compiled block-Lanczos loop over the fused operator holds no
    host callback (the persistent compile cache stores no program with
    one); its passes reach the counters from the host instead."""
    import jax

    from repro.cluster.affinity import build_fused_rbf_operator
    from repro.core import lanczos as lz
    from repro.distrib import mesh_utils
    op = build_fused_rbf_operator(unit_rows(256, 64, 3), 1.0,
                                  mesh_utils.local_mesh("rows"))
    assert isinstance(op.matmat, jax.tree_util.Partial)
    state = lz.init_block_state(op.n_pad, 2, jax.random.PRNGKey(0), 4)
    hlo = lz._block_steps_jit.lower(op.matmat, state, 2).as_text()
    assert "callback" not in hlo.lower()
    est = SpectralClustering(3, affinity="fused-rbf", seed=0,
                             eigensolver="block-lanczos", block_size=4,
                             lanczos_steps=8)
    obs.reset()
    est.fit(unit_rows(256, 64, 3))
    counts = {k: m["value"] for k, m in obs.snapshot().items()
              if k.startswith("fused.passes")}
    assert counts == {"fused.passes{width=5}": 1,
                      "fused.passes{width=4}": 1}
    assert est.info_["engine"]["matrix_passes"] == 2


def test_spectral_job_refuses_krylov_space_below_k(capsys):
    from repro.launch import spectral_job
    with pytest.raises(SystemExit):
        spectral_job.main(["--blobs", "60", "--k", "50"])
    assert "below --k" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        spectral_job.main(["--graph", "g.txt", "--points", "p.npy"])


def test_autotune_tries_feature_tiles():
    from repro.tune.autotune import candidates
    cands = candidates("fused_rbf_matmat", n=4096, m=4096, d=4096, b=8)
    assert cands[0].bd == frm.default_d_tile(4096)
    assert {256, 512} <= {c.bd for c in cands}
    assert {c.bd for c in candidates("fused_rbf_matmat", quick=True, n=512,
                                     m=512, d=8, b=8)} == {8}
