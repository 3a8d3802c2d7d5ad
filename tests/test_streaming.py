"""Streaming out-of-core ingest (repro.data.streaming, DESIGN.md §10):
stream-vs-memory bit-identity across shard counts, dedup semantics, the
triplet-file reader, incremental bucket patterns, and the netflix_like
duplicate-inflation regression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import streaming, synthetic
from repro.data.pipeline import CompletionDataset
from repro.sparse.ccsr import IncrementalBucketBuilder, bucket_pattern

SHAPE = (40, 30, 12)


def _chunks(seed=7, nnz=5000, chunk=1200, kind="function", shape=SHAPE):
    gen = (streaming.function_stream if kind == "function"
           else streaming.netflix_stream)
    return list(gen(seed, shape, nnz, chunk))


# ---------------------------------------------------------------------------
# bit-identity: streamed chunks == in-memory, across 1/2/4 shards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["function", "netflix"])
def test_streamed_ingest_bit_identical_across_shards(kind):
    """CompletionDataset built from streamed chunks is bit-identical to the
    in-memory path (all chunks materialized as ONE slab) on the same seed,
    for 1/2/4 shards — global gather comparison, exact equality."""
    chunks = _chunks(kind=kind)
    big = streaming.Chunk(np.concatenate([c.indices for c in chunks]),
                          np.concatenate([c.values for c in chunks]))
    ds_mem = CompletionDataset.from_stream(iter([big]), SHAPE, num_shards=1)
    want_idx, want_vals = ds_mem.gather_global()
    assert want_idx.shape[0] == ds_mem.tensor.nnz > 0
    for shards in (1, 2, 4):
        ds = CompletionDataset.from_stream(iter(chunks), SHAPE,
                                           num_shards=shards)
        gi, gv = ds.gather_global()
        assert np.array_equal(gi, want_idx), f"{shards} shards: indices"
        assert np.array_equal(gv, want_vals), f"{shards} shards: values"
        assert ds.tensor.nnz == want_idx.shape[0]
        # streamed metadata becomes the planner's hints
        assert ds.tensor.nnz_rows == ds.stats.nnz_rows
        assert ds.stats.shard_nnz and sum(ds.stats.shard_nnz) == ds.tensor.nnz


def test_streamed_matches_shuffled_inmemory_entry_set():
    """The streamed path holds the same entry SET as the classic
    shuffle-and-pad ingest of the deduped tensor (layouts differ)."""
    chunks = _chunks()
    ds = CompletionDataset.from_stream(iter(chunks), SHAPE, num_shards=2)
    gi, gv = ds.gather_global()
    # classic path over the same (deduped) entries
    st = streaming.pack_shards(
        [streaming.StreamingIngest(SHAPE, 1).consume(chunks).finalize_shard(0)],
        SHAPE)
    ds2 = CompletionDataset(st, jax.random.PRNGKey(0))
    gi2, gv2 = ds2.gather_global()
    assert np.array_equal(gi, gi2) and np.array_equal(gv, gv2)


def test_first_occurrence_wins_across_chunks():
    """Cross-chunk duplicate coordinates keep the FIRST stream value."""
    idx = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    c1 = streaming.Chunk(idx, np.array([10.0, 20.0], np.float32))
    c2 = streaming.Chunk(idx[:1], np.array([99.0], np.float32))
    ing = streaming.StreamingIngest(SHAPE, 1)
    ing.add(c1)
    ing.add(c2)
    shards, stats = ing.finalize()
    assert stats.nnz == 2 and stats.duplicates_dropped == 1
    (si, sv) = shards[0]
    row = np.nonzero((si == idx[0]).all(axis=1))[0]
    assert sv[row] == 10.0


def test_spool_dir_out_of_core_equivalent(tmp_path):
    """Spilled (out-of-core) ingest produces the identical dataset."""
    chunks = _chunks()
    ds_mem = CompletionDataset.from_stream(iter(chunks), SHAPE, num_shards=4)
    ds_ooc = CompletionDataset.from_stream(iter(chunks), SHAPE, num_shards=4,
                                           spool_dir=str(tmp_path))
    for a, b in zip(ds_mem.gather_global(), ds_ooc.gather_global()):
        assert np.array_equal(a, b)
    assert any(f.endswith(".npz") for f in os.listdir(tmp_path))


# ---------------------------------------------------------------------------
# split + evaluation
# ---------------------------------------------------------------------------

def test_split_is_deterministic_and_disjoint():
    chunks = _chunks(kind="netflix")
    train, test, stats = streaming.ingest(iter(chunks), SHAPE, num_shards=2,
                                          test_fraction=0.2)
    def lin_set(st):
        idx = np.asarray(st.indices)[np.asarray(st.valid)]
        return set(streaming._linearize64(idx, SHAPE).tolist())
    tr, te = lin_set(train), lin_set(test)
    assert tr and te and not (tr & te)
    frac = len(te) / (len(te) + len(tr))
    assert 0.1 < frac < 0.3
    # same split on re-ingest
    _, test2, _ = streaming.ingest(iter(chunks), SHAPE, num_shards=1,
                                   test_fraction=0.2)
    assert lin_set(test2) == te


def test_heldout_metrics_perfect_model():
    """A rank-1 factorization of its own TTTP has ~zero held-out error."""
    key = jax.random.PRNGKey(0)
    fs = [jnp.abs(jax.random.normal(k, (d, 1))) + 0.5
          for k, d in zip(jax.random.split(key, 3), SHAPE)]
    idx = np.stack(np.unravel_index(np.arange(0, 600, 7),
                                    SHAPE), 1).astype(np.int32)
    from repro.core.sparse_tensor import SparseTensor
    from repro.core.tttp import multilinear_values
    st = SparseTensor.from_coo(idx, np.ones(idx.shape[0], np.float32), SHAPE)
    st = st.with_values(multilinear_values(st, fs))
    m = streaming.heldout_metrics(st, fs)
    assert m["rmse"] < 1e-5
    assert m["count"] == idx.shape[0]
    # log link evaluates exp(model)
    fs_log = [jnp.zeros((d, 1)) for d in SHAPE]
    st1 = st.with_values(jnp.ones_like(st.values))
    m_log = streaming.heldout_metrics(st1, fs_log, link="log")
    assert m_log["rmse"] < 1e-5


def test_heldout_metrics_log_clamp_region():
    """Model values beyond ±30 are clamped BEFORE exp: a huge positive
    log-rate yields exp(30), not inf, and huge negatives stay finite."""
    from repro.core.sparse_tensor import SparseTensor
    rng = np.random.default_rng(3)
    idx = np.stack([rng.integers(0, s, size=32) for s in SHAPE],
                   axis=1).astype(np.int32)
    st = SparseTensor.from_coo(idx, np.ones(32, np.float32), SHAPE)
    for sign in (+1.0, -1.0):
        # rank-1 all-constant factors: model value = sign * 100 everywhere
        fs = [jnp.full((d, 1), c) for d, c in
              zip(SHAPE, (sign * 100.0, 1.0, 1.0))]
        m = streaming.heldout_metrics(st, fs, link="log")
        assert np.isfinite(m["rmse"]) and np.isfinite(m["poisson_deviance"])
        pred = np.exp(sign * 30.0)       # the clamp boundary value
        np.testing.assert_allclose(m["rmse"], abs(pred - 1.0), rtol=1e-4)
    # inside the clamp region the link is exactly exp(model)
    fs = [jnp.full((d, 1), c) for d, c in zip(SHAPE, (2.0, 1.0, 1.0))]
    m = streaming.heldout_metrics(st, fs, link="log")
    np.testing.assert_allclose(m["rmse"], np.exp(2.0) - 1.0, rtol=1e-4)


def test_heldout_metrics_all_masked():
    """A fully-padded (zero valid entries) tensor must not divide by zero
    or poison the metrics with padding rows."""
    from repro.core.sparse_tensor import SparseTensor
    st = SparseTensor.from_coo(np.zeros((0, 3), np.int32),
                               np.zeros((0,), np.float32), SHAPE, cap=16)
    assert int(np.sum(np.asarray(st.mask))) == 0
    fs = [jnp.ones((d, 2)) for d in SHAPE]
    m = streaming.heldout_metrics(st, fs)
    assert m["count"] == 0 or m["count"] == 1   # n clamped to >= 1
    assert m["rmse"] == 0.0
    assert m["poisson_deviance"] == 0.0
    assert np.isfinite(m["rmse"])


# ---------------------------------------------------------------------------
# triplet file reader
# ---------------------------------------------------------------------------

def test_triplet_file_stream_roundtrip(tmp_path):
    chunks = _chunks(nnz=800, chunk=300)
    path = tmp_path / "triplets.txt"
    with open(path, "w") as f:
        f.write("# i j k value\n")
        for c in chunks:
            for (i, j, k), v in zip(c.indices, c.values):
                f.write(f"{i} {j} {k} {v}\n")
    read = list(streaming.triplet_file_stream(str(path), ndim=3,
                                              chunk_size=256))
    assert sum(len(c) for c in read) == sum(len(c) for c in chunks)
    got_idx = np.concatenate([c.indices for c in read])
    want_idx = np.concatenate([c.indices for c in chunks])
    assert np.array_equal(got_idx, want_idx)
    ds_file = CompletionDataset.from_stream(iter(read), SHAPE, num_shards=2)
    ds_mem = CompletionDataset.from_stream(iter(chunks), SHAPE, num_shards=2)
    gi, gv = ds_file.gather_global()
    mi, mv = ds_mem.gather_global()
    assert np.array_equal(gi, mi)
    np.testing.assert_allclose(gv, mv, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# incremental bucket patterns
# ---------------------------------------------------------------------------

def test_incremental_bucket_pattern_matches_direct():
    """Streamed occupancy counts give the same bucket view as the direct
    host-side build (capacity may be padded up, pattern content equal)."""
    chunks = _chunks(nnz=2000, chunk=500)
    ds = CompletionDataset.from_stream(iter(chunks), SHAPE, num_shards=1,
                                       block_rows=8)
    st = ds.tensor
    for mode in range(st.ndim):
        got = st.row_buckets(mode, 8)          # served from the ingest cache
        direct = bucket_pattern(
            SparseTensor_copy(st), mode, 8).gather(st)
        assert got.values.shape[1] >= direct.values.shape[1]
        cap = direct.values.shape[1]
        np.testing.assert_allclose(np.asarray(got.values)[:, :cap],
                                   np.asarray(direct.values))
        assert not np.asarray(got.valid)[:, cap:].any()


def SparseTensor_copy(st):
    """Pattern-cache-free copy (forces a direct rebuild)."""
    from repro.core.sparse_tensor import SparseTensor
    return SparseTensor(st.indices, st.values, st.valid, st.shape, st.nnz,
                        st.sorted_mode)


def test_incremental_builder_counts_are_upper_bounds():
    chunks = _chunks(nnz=3000, chunk=700)
    ing = streaming.StreamingIngest(SHAPE, 2, block_rows=8)
    for c in chunks:
        ing.add(c)
    shards, stats = ing.finalize()
    st = streaming.pack_shards(shards, SHAPE, stats)
    assert stats.bucket_block_rows == 8
    for mode in range(3):
        actual = np.bincount(
            np.asarray(st.indices)[np.asarray(st.valid)][:, mode] // 8,
            minlength=stats.bucket_counts[mode].shape[0])
        assert (stats.bucket_counts[mode] >= actual).all()


def test_incremental_builder_build_matches_direct():
    """builder.build (streamed-capacity pattern) gathers the same buckets
    as a direct build, padded up to the streamed capacity."""
    chunks = _chunks(nnz=1200, chunk=300)
    b = IncrementalBucketBuilder(SHAPE, 8)
    for c in chunks:
        b.observe(c.indices)
    sh = streaming.StreamingIngest(SHAPE, 1).consume(chunks).finalize_shard(0)
    st = streaming.pack_shards([sh], SHAPE)
    for mode in range(3):
        got = b.build(st, mode).gather(st)
        direct = bucket_pattern(SparseTensor_copy(st), mode, 8).gather(st)
        cap = direct.values.shape[1]
        assert got.values.shape[1] >= cap
        np.testing.assert_allclose(np.asarray(got.values)[:, :cap],
                                   np.asarray(direct.values))
        assert not np.asarray(got.valid)[:, cap:].any()


def test_sorted_mode_fast_path_matches_unsorted():
    """bucket_pattern's argsort-skip for sorted tensors is bit-equivalent."""
    chunks = _chunks(nnz=1500, chunk=400)
    sh = streaming.StreamingIngest(SHAPE, 1).consume(chunks).finalize_shard(0)
    st_sorted = streaming.pack_shards([sh], SHAPE)        # sorted_mode=0
    assert st_sorted.sorted_mode == 0
    st_plain = SparseTensor_copy(st_sorted)
    object.__setattr__(st_plain, "sorted_mode", None)
    a = bucket_pattern(st_sorted, 0, 8)
    b = bucket_pattern(st_plain, 0, 8)
    for f in ("sel", "indices", "local_row", "valid"):
        assert np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))), f


_MESH_SCRIPT = r"""
import jax, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.distributed import make_mesh
from repro.data import streaming
from repro.data.pipeline import CompletionDataset
from repro.core.completion import als_sweep
from repro.core.distributed import DistLayout

mesh = make_mesh((4,), ("data",))
shape = (40, 32, 12)
chunks = list(streaming.function_stream(5, shape, 8000, 2000))
ds = CompletionDataset.from_stream(iter(chunks), shape, mesh=mesh,
                                   bucket_modes=())
layout = DistLayout(mesh, ("data",), None)
st_spec = layout.sparse_specs(ds.tensor)
fs = [jax.random.normal(k, (d, 4))
      for k, d in zip(jax.random.split(jax.random.PRNGKey(0), 3), shape)]
fn = jax.jit(shard_map(
    lambda s, o, f: tuple(als_sweep(s, o, list(f), 1e-4, ctx=layout.ctx)),
    mesh=mesh, in_specs=(st_spec, st_spec, (P(None, None),) * 3),
    out_specs=(P(None, None),) * 3, check_vma=False))
out = fn(ds.tensor, ds.omega, tuple(fs))
ds_l = CompletionDataset.from_stream(iter(chunks), shape, num_shards=1,
                                     bucket_modes=())
out_l = jax.jit(lambda s, o, f: tuple(als_sweep(s, o, list(f), 1e-4)))(
    ds_l.tensor, ds_l.omega, tuple(fs))
for a, b in zip(out, out_l):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)
print("MESH_STREAM_OK")
"""


@pytest.mark.slow
def test_streamed_dataset_under_mesh_matches_local():
    """from_stream(mesh=...) feeds shard_map ALS with results matching the
    single-shard LOCAL ingest (subprocess: needs 4 forced host devices)."""
    import subprocess
    import sys
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "MESH_STREAM_OK" in res.stdout


# ---------------------------------------------------------------------------
# netflix_like duplicate-inflation regression (in-memory generator)
# ---------------------------------------------------------------------------

def test_netflix_like_exact_nnz_no_duplicates():
    """Zipf sampling repeats coordinates; the fixed generator dedups and
    returns EXACTLY the requested nnz unique entries (regression pin)."""
    st = synthetic.netflix_like(jax.random.PRNGKey(0), (50, 40, 10), nnz=2000)
    assert st.nnz == 2000
    assert int(np.asarray(st.valid).sum()) == 2000
    idx = np.asarray(st.indices)[np.asarray(st.valid)]
    lin = streaming._linearize64(idx, (50, 40, 10))
    assert np.unique(lin).size == 2000              # Ω is a set
    vals = np.asarray(st.values)[np.asarray(st.valid)]
    assert vals.min() >= 1.0 and vals.max() <= 5.0


def test_netflix_like_rejects_impossible_density():
    with pytest.raises(ValueError):
        synthetic.netflix_like(jax.random.PRNGKey(0), (4, 4, 4), nnz=100)


# ---------------------------------------------------------------------------
# memory boundedness (scaled-down smoke of the 50M benchmark claim)
# ---------------------------------------------------------------------------

def test_metadata_only_ingest_is_chunk_bounded():
    """keep_entries=False drops each chunk after metadata extraction —
    nothing accumulates, so a stream much larger than any chunk completes
    with peak host memory strictly O(chunk) (the 50M-nnz benchmark claim,
    measured for real in benchmarks/bench_ingest.py)."""
    shape = (5000, 4000, 300)
    ing = streaming.StreamingIngest(shape, 8, block_rows=64,
                                    keep_entries=False)
    ing._runs = None                 # hard proof: storing a run would crash
    for c in streaming.function_stream(3, shape, 200_000, 50_000):
        ing.add(c)
    stats = ing.finalize_stats()
    assert stats.nnz == stats.entries_kept > 190_000
    assert all(r > 0 for r in stats.nnz_rows)
    assert stats.bucket_counts is not None
