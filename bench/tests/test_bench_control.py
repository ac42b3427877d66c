"""The control: the plain reference computed in bfloat16, below the
float32 the configurations state, has to come out as not correct."""
import pytest

from bench import compare, control
from bench.loader import Bench

from .conftest import TINY_CELL


@pytest.mark.parametrize("seed", [2**33 + 1, 2**31 + 7, 12345])
def test_bfloat16_control_fails_the_comparison(tiny_root, seed):
    values = control.control_readings(Bench(tiny_root), TINY_CELL, seed, 20)
    correct, checks = compare.verdict(values, failed=0)
    assert not correct
    # the float numbers are the ones it fails: pagerank and sssp
    assert values["pagerank_max_rel_err"] > 10 * compare.LIMITS[
        "pagerank_max_rel_err"]
    assert values["sssp_mismatches"] > 0


def test_float32_reference_in_the_controls_place_passes(tiny_root):
    values = control.control_readings(Bench(tiny_root), TINY_CELL, 99, 20,
                                      dtype="float32")
    # float32 is the stated precision: exact apps exact, pagerank close
    assert values["bfs_mismatches"] == values["sssp_mismatches"] == 0
    assert values["pagerank_max_rel_err"] <= compare.LIMITS[
        "pagerank_max_rel_err"]
