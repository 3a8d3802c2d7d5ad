"""The data generators: the Netflix count profiles hold the published
statistics they were fitted to, and a seed always makes the same data."""
import json

import jax
import numpy as np
import pytest

import harness
from conftest import CHECKOUT

NETFLIX = json.loads((CHECKOUT / "chipbench/configs/netflix.json")
                     .read_text())
gen = harness.load_module(harness.ROOT / "generators" / "fitted_counts.py")


@pytest.mark.parametrize("mode", [0, 1])
def test_count_profile_holds_the_published_statistics(mode):
    c = NETFLIX["counts"][mode]
    n = NETFLIX["shape"][mode]
    prof = gen.count_profile(n, c["median"], c["min"], c["max"],
                             NETFLIX["nnz_total"])
    assert prof.shape == (n,)
    assert np.all(np.diff(prof) <= 0)
    assert np.median(prof) == c["median"]
    assert prof.max() == c["max"] and prof.min() == c["min"]
    assert prof.sum() == pytest.approx(NETFLIX["nnz_total"], rel=1e-4)


def test_count_profile_refuses_statistics_no_profile_meets():
    with pytest.raises(ValueError):
        gen.count_profile(100, 50, 1, 60, 100 * 70)


def test_share_has_distinct_pairs_and_follows_the_seed():
    cfg = dict(NETFLIX, shape=[3000, 500, 60], nnz_total=640000)
    key = harness.seed_key(2 ** 31 + 17)
    idx, vals = gen.generate(key, cfg, 20000)
    idx, vals = np.asarray(idx), np.asarray(vals)
    assert idx.shape == (20000, 3) and vals.shape == (20000,)
    assert len({(u, m) for u, m, _ in idx.tolist()}) == 20000
    assert np.all((idx >= 0) & (idx < np.array(cfg["shape"])))
    assert set(np.unique(vals)) <= {1.0, 2.0, 3.0, 4.0, 5.0}
    again, _ = gen.generate(key, cfg, 20000)
    assert np.array_equal(idx, np.asarray(again))
    other, _ = gen.generate(jax.random.fold_in(key, 1), cfg, 20000)
    assert not np.array_equal(idx, np.asarray(other))
