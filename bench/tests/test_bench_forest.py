"""The benchmark's forest generator against the Covertype shape it states:
integer attributes in their published ranges, 4 wilderness areas, 40 soil
types, duplicates that permute their base column, and templates of the
stated depths and sizes on distinct columns."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0:0] = [ROOT]

from bench.data import forest  # noqa: E402
from bench.reference import columns_of  # noqa: E402


@pytest.fixture(scope="module")
def data():
    return forest.build({"rows": 50_000, "n_dup": 3}, seed=2**36 + 5)


def test_attributes_follow_covertype(data):
    assert len(data.columns) == 12 * 3
    for name, (dist, mean, sd, lo, hi) in forest.QUANT.items():
        col = data.columns[f"{name}_0"]
        assert col.dtype == np.float32
        assert np.array_equal(col, np.rint(col)), name
        assert col.min() >= lo and col.max() <= hi, name
        if dist != "uniform":           # aspect: uniform over 0-360
            assert abs(col.mean() - mean) < 0.2 * sd, name
    assert set(np.unique(data.columns["wilderness_0"])) <= set(range(4))
    assert len(np.unique(data.columns["soil_0"])) > 30
    assert data.columns["soil_0"].max() < 40


def test_duplicates_permute_their_base(data):
    for name in forest.QUANT_BASE + ["soil"]:
        a, b = data.columns[f"{name}_0"], data.columns[f"{name}_2"]
        assert not np.array_equal(a, b)
        assert np.array_equal(np.sort(a), np.sort(b))
        assert data.distinct(f"{name}_2") == len(np.unique(a))


def test_templates_have_their_sizes(data):
    rng = np.random.default_rng(3)
    draw = data.family("forest_templates",
                       {"templates": 8, "zipf_s": 1.0, "depths": [2, 4],
                        "atoms": [12, 16]}, rng)
    for _ in range(20):
        q = draw(rng)
        atoms = []

        def walk(node):
            if node[0] == "atom":
                atoms.append(node)
            else:
                for c in node[1]:
                    walk(c)
        walk(q)
        assert 12 <= len(atoms) <= 16
        assert len(columns_of(q)) == len(atoms)
