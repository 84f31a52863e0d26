"""The graph cell's comparison, run through the harness at a size a test
run can hold: sound runs come out correct, and the controls and every
planted fault come out not correct.

The chip runs the controls at the cell's own size
(``perfbench/tools/controls.py``); here the harness's look for a chip is
skipped and the CPU's first device stands in.
"""
import copy

import pytest

from perfbench import controls, harness
from perfbench import run as bench_run

SEED = 2 ** 31 + 11


def small():
    """The graph cell at a quarter of the paper's vertices and edges."""
    cell = copy.deepcopy(harness.find_cell(harness.load_benchmark(),
                                           "fit.paper-graph"))
    cell.config.update(vertices=2500, edges=5250)
    return cell


def run(cell, seconds=0.5):
    import jax
    return bench_run.run(cell, SEED, seconds, False, jax.devices()[:1])


@pytest.mark.parametrize("workload", ["fit.paper-graph"])
def test_sound_run_is_correct(workload):
    res = run(small())
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert "setup_s" in res["metrics"]


def not_correct(broken, number):
    with broken:
        res = run(small())
    assert not res["correct"], res["checks"]
    check = res["checks"][number]
    assert check["value"] is None or check["value"] > check["limit"]


@pytest.mark.parametrize("workload", ["fit.paper-graph"])
def test_an_altered_answer_is_not_correct(workload):
    not_correct(controls.labels_altered(), "label_gap")


def test_altered_eigenvalues_are_not_correct():
    not_correct(controls.eigenvalues_altered(), "ritz_gap")


def test_the_one_pass_control_is_not_correct_on_the_graph():
    not_correct(controls.products_at(1), "resid_first")


BROKEN = {
    # the configuration states float32 at HIGHEST: three bfloat16 passes
    # are its control
    "three_passes": (lambda: controls.products_at(3), "resid_first"),
    "step_unchanged": (controls.step_unchanged, "ritz_gap"),
    "half_rows": (controls.half_rows, "resid_first"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_path_is_not_correct(fault):
    make, number = BROKEN[fault]
    not_correct(make(), number)
