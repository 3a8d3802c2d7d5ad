"""A whole run of the ``netflix.als`` cell on the CPU, cut small: the
program's sweeps read correct, the bfloat16 control and the planted
faults do not.

``tiny.py`` cuts each configuration of ``BENCHMARK.json`` by its table
``TINY``; the ``netflix`` entry is given here, when pytest collects this
module, so the other tests of this directory that cut every
configuration find it too. The cut keeps the published per-user and
per-movie counts at 4,000 users, 120 movies and 40 days with 209 ratings
a user in the whole data set, 16,000 of them drawn: the user mode keeps
the COO matvec, the movie and day modes take the row-slab operator, as at
the cell's size."""
import time

import pytest

import harness
import tiny
from tiny import tiny_bench

tiny.TINY.setdefault("netflix", {"shape": [4000, 120, 40],
                                 "nnz_total": 4000 * 209,
                                 "nnz_per_chip": 16000})


def _run(tmp_path, tamper=None, seed=2 ** 31 + 11):
    return harness.run("netflix.als", seed, 0.5, False, time.perf_counter(),
                       bench_path=tiny_bench(tmp_path), require_tpu=False,
                       tamper=tamper)


def test_sound_run_is_correct(tmp_path):
    res = _run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    for info in res["detail"]["reference"]:
        assert info["rows"] >= 0.9 * info["rows_all"], info


@pytest.mark.parametrize("fault", ["control", "unchanged", "half",
                                   "altered"])
def test_fault_is_not_correct(tmp_path, fault):
    res = _run(tmp_path, tamper=fault)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
