"""The kernel-level roofline peak model (repro.tune.peaks) and the
schedule-equivalence property: any legal schedule computes the same
function as the default, within 1e-4 in f32 — the contract that makes the
autotuner's search safe by construction.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels import ops, ref
from repro.tune import Schedule, peaks


# ---------------------------------------------------------------------------
# peak model sanity (CPU interpret path: numbers must be consistent, the
# absolute peaks are a yardstick, not a silicon claim)


def test_device_peaks_lookup():
    assert peaks.device_peaks("cpu") == peaks.DEVICE_PEAKS["cpu"]
    # the v5e row is keyed by the normalized kind JAX reports on the chip
    assert peaks.device_peaks("tpu-v5-lite")["flops"] == 197e12
    assert peaks.device_peaks("tpu-v5-lite")["bytes"] == 819e9
    # a kind the table does not list is an error, never a default row
    for kind in ("tpu-v5e", "tpu-v9", "gpu-x"):
        with pytest.raises(KeyError, match="no published peaks"):
            peaks.device_peaks(kind)
    # None = current backend; this suite runs on CPU
    assert peaks.device_peaks() == peaks.DEVICE_PEAKS["cpu"]


def test_kernel_roofline_fractions():
    cpu = peaks.DEVICE_PEAKS["cpu"]
    # exactly one second at exactly half of each peak
    rec = peaks.kernel_roofline(cpu["flops"] / 2, cpu["bytes"] / 2, 1.0,
                                kind="cpu")
    assert abs(rec["frac_peak_flops"] - 0.5) < 1e-6
    assert abs(rec["frac_peak_bytes"] - 0.5) < 1e-6
    assert rec["gflops"] == round(cpu["flops"] / 2 / 1e9, 2)


def test_kernel_roofline_dominant_bottleneck():
    cpu = peaks.DEVICE_PEAKS["cpu"]
    # lots of flops, few bytes -> compute-bound; and vice versa
    hi_flops = peaks.kernel_roofline(cpu["flops"], 1.0, 1.0, kind="cpu")
    hi_bytes = peaks.kernel_roofline(1.0, cpu["bytes"], 1.0, kind="cpu")
    assert hi_flops["dominant"] == "compute"
    assert hi_bytes["dominant"] == "memory"


def test_kernel_roofline_never_divides_by_zero():
    rec = peaks.kernel_roofline(1e9, 1e6, 0.0, kind="cpu")
    assert np.isfinite(rec["gflops"])


def test_spec_models_positive_for_defaults():
    from repro.tune import KERNELS
    shapes = {"rbf_similarity": dict(n=256, m=256, d=8),
              "fused_rbf_matmat": dict(n=256, m=256, d=8, b=8),
              "fused_nystrom_matmat": dict(n=256, m=256, d=8, b=8),
              "block_matmat": dict(n=256, m=256, b=8),
              "kmeans_assign": dict(n=256, d=8, k=8)}
    for name, sp in KERNELS.items():
        s = sp.default
        assert sp.flops_model(s, **shapes[name]) > 0
        assert sp.bytes_model(s, **shapes[name]) > 0
        assert sp.vmem_model(s, **shapes[name]) > 0


# ---------------------------------------------------------------------------
# schedule-equivalence property: legal schedule == default, <= 1e-4
# (indices into candidate tile lists; no deadline: the first example pays
# the interpret-mode compile)

_TILES = (8, 16, 32, 64)
_ACCS = ("inplace", "scratch")

_x = jnp.asarray(np.random.RandomState(0).randn(96, 5).astype(np.float32))
_y = jnp.asarray(np.random.RandomState(1).randn(80, 5).astype(np.float32))
_V = jnp.asarray(np.random.RandomState(2).randn(80, 4).astype(np.float32))
_A = jnp.asarray(np.random.RandomState(3).randn(96, 80).astype(np.float32))

_FUSED_DEFAULT = np.asarray(ops.fused_rbf_matmat(_x, _y, _V, 0.9))
_MATMAT_DEFAULT = np.asarray(ops.block_matmat(_A, _V))


@settings(max_examples=12, deadline=None)
@given(st.integers(0, len(_TILES) - 1), st.integers(0, len(_TILES) - 1),
       st.integers(0, 1))
def test_fused_rbf_schedule_equivalence(bi, bj, ai):
    s = Schedule(bm=_TILES[bi], bn=_TILES[bj], acc=_ACCS[ai])
    got = np.asarray(ops.fused_rbf_matmat(_x, _y, _V, 0.9, schedule=s))
    np.testing.assert_allclose(got, _FUSED_DEFAULT, atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, len(_TILES) - 1), st.integers(0, len(_TILES) - 1),
       st.integers(0, 1))
def test_block_matmat_schedule_equivalence(bi, bj, ai):
    s = Schedule(bm=_TILES[bi], bn=_TILES[bj], acc=_ACCS[ai])
    got = np.asarray(ops.block_matmat(_A, _V, schedule=s))
    np.testing.assert_allclose(got, _MATMAT_DEFAULT, atol=1e-4)


def test_equivalence_against_oracle():
    # the defaults themselves are right (anchors the property tests)
    want = np.asarray(ref.rbf_similarity(_x, _y, 0.9)) @ np.asarray(_V)
    np.testing.assert_allclose(_FUSED_DEFAULT, want, atol=1e-4)
    np.testing.assert_allclose(_MATMAT_DEFAULT,
                               np.asarray(_A) @ np.asarray(_V), atol=1e-4)
