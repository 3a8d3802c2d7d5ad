"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracles in
repro.kernels.ref, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse_tensor import SparseTensor
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.sparse.ccsr import bucketize

SHAPES = [((13, 9, 7), 50), ((64, 32, 16), 500), ((40, 40, 40, 40), 300),
          ((128, 8), 200)]
RANKS = [1, 8, 96]
DTYPES = [jnp.float32, jnp.bfloat16]


def _mk(key, shape, nnz, r, dtype):
    st = SparseTensor.random(key, shape, nnz, cap=nnz + 37, dtype=jnp.float32)
    st = st.astype(dtype)
    ks = jax.random.split(key, len(shape))
    factors = [jax.random.normal(k, (d, r), dtype) for k, d in zip(ks, shape)]
    return st, factors


@pytest.mark.parametrize("shape,nnz", SHAPES)
@pytest.mark.parametrize("r", RANKS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_tttp_kernel_matches_ref(shape, nnz, r, dtype):
    st, factors = _mk(jax.random.PRNGKey(0), shape, nnz, r, dtype)
    got = kops.tttp_values(st, factors, use_pallas=True, block_m=64,
                           block_r=32)
    want = kref.tttp_ref(st.values * st.mask, st.indices, factors)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("shape,nnz", SHAPES[:3])
@pytest.mark.parametrize("r", RANKS)
def test_tttp_partial_factors(shape, nnz, r):
    st, factors = _mk(jax.random.PRNGKey(1), shape, nnz, r, jnp.float32)
    factors[1] = None
    got = kops.tttp_values(st, factors, use_pallas=True, block_m=64,
                           block_r=32)
    want = kref.tttp_ref(st.values * st.mask, st.indices, factors)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("shape,nnz", SHAPES[:3])
@pytest.mark.parametrize("r", [8, 96])
@pytest.mark.parametrize("mode", [0, 1])
def test_mttkrp_kernel_matches_dense_oracle(shape, nnz, r, mode):
    st, factors = _mk(jax.random.PRNGKey(2), shape, nnz, r, jnp.float32)
    bk = bucketize(st, mode, block_rows=8)
    fac = list(factors)
    fac[mode] = None
    got = kops.mttkrp_bucketed(bk, fac, use_pallas=True, block_r=32)
    dense = st.todense()
    letters = "ijkl"[:st.ndim]
    expr = (letters + "," +
            ",".join(f"{letters[d]}r" for d in range(st.ndim) if d != mode)
            + f"->{letters[mode]}r")
    want = jnp.einsum(expr, dense, *[factors[d] for d in range(st.ndim)
                                     if d != mode])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape,nnz", SHAPES[:2])
@pytest.mark.parametrize("r", [4, 32])
def test_cg_matvec_kernel_matches_gram(shape, nnz, r):
    """Fused implicit matvec == explicit Gram matvec (paper eq. 3)."""
    key = jax.random.PRNGKey(3)
    st, factors = _mk(key, shape, nnz, r, jnp.float32)
    omega = st.with_values(jnp.ones_like(st.values))
    bk = bucketize(omega, 0, block_rows=8)
    fac = [None] + factors[1:]
    x = jax.random.normal(key, (shape[0], r))
    got = kops.cg_matvec_bucketed(bk, fac, x, use_pallas=True)
    # explicit G^(i): kr_n = prod of other-mode rows
    kr = jnp.ones((omega.cap, r))
    for d in range(1, st.ndim):
        kr = kr * factors[d][st.indices[:, d]]
    kr = kr * omega.mask[:, None]
    gram = jax.ops.segment_sum(kr[:, :, None] * kr[:, None, :],
                               st.indices[:, 0], num_segments=shape[0])
    want = jnp.einsum("irs,is->ir", gram, x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_bucket_capacity_validation():
    st = SparseTensor.random(jax.random.PRNGKey(4), (16, 8, 4), 100)
    with pytest.raises(ValueError):
        bucketize(st, 0, block_rows=4, capacity=2)


def test_pallas_vs_jnp_dispatch_agree():
    st, factors = _mk(jax.random.PRNGKey(5), (32, 16, 8), 200, 16,
                      jnp.float32)
    a = kops.tttp_values(st, factors, use_pallas=True, block_m=64, block_r=16)
    b = kops.tttp_values(st, factors, use_pallas=False)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# tile tier (DESIGN.md §13): KernelTile-parameterized schedules and blocking
# ---------------------------------------------------------------------------

from repro.kernels.tile import KernelTile, onehot_break_even, scatter_rows


def test_scatter_schedules_agree():
    """The segmented-reduction scatter is a drop-in for the one-hot matmul,
    including padding slots (key == block_rows falls off the end)."""
    key = jax.random.PRNGKey(7)
    prod = jax.random.normal(key, (64, 16))
    rows = jnp.sort(jax.random.randint(key, (64,), 0, 9))  # 8 = padding
    a = scatter_rows(prod, rows, 8, "onehot", jnp.float32)
    b = scatter_rows(prod, rows, 8, "segmented", jnp.float32)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_break_even_monotone():
    assert onehot_break_even(2048) > onehot_break_even(256) > 0
    assert KernelTile(schedule="auto").resolved_schedule(8, 1024) == "onehot"
    big = onehot_break_even(1024) + 8
    assert KernelTile(schedule="auto").resolved_schedule(big, 1024) \
        == "segmented"


@pytest.mark.parametrize("schedule", ["onehot", "segmented"])
@pytest.mark.parametrize("g", [1, 3])
def test_mttkrp_tile_schedules_match_ref(schedule, g):
    st, factors = _mk(jax.random.PRNGKey(8), (64, 32, 16), 500, 16,
                      jnp.float32)
    bk = bucketize(st, 0, block_rows=8)
    fac = [None] + factors[1:]
    tile = KernelTile(block_m=64, schedule=schedule, buckets_per_step=g)
    got = kops.mttkrp_bucketed(bk, fac, num_rows=64, use_pallas=True,
                               tile=tile)
    want = kops.mttkrp_bucketed(bk, fac, num_rows=64, use_pallas=False)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("schedule", ["onehot", "segmented"])
@pytest.mark.parametrize("g", [1, 2])
def test_cg_matvec_tile_schedules_match_ref(schedule, g):
    key = jax.random.PRNGKey(9)
    st, factors = _mk(key, (64, 32, 16), 500, 16, jnp.float32)
    omega = st.with_values(jnp.ones_like(st.values))
    bk = bucketize(omega, 0, block_rows=8)
    fac = [None] + factors[1:]
    x = jax.random.normal(key, (64, 16))
    tile = KernelTile(block_m=64, schedule=schedule, buckets_per_step=g)
    got = kops.cg_matvec_bucketed(bk, fac, x, num_rows=64, use_pallas=True,
                                  tile=tile)
    want = kops.cg_matvec_bucketed(bk, fac, x, num_rows=64, use_pallas=False)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_capacity_not_multiple_of_block_m():
    """Bucket capacity that doesn't divide the capacity tile gets padded
    inside the pallas wrappers (padding slots carry valid=0)."""
    st, factors = _mk(jax.random.PRNGKey(10), (40, 24, 12), 300, 8,
                      jnp.float32)
    bk = bucketize(st, 0, block_rows=8)
    fac = [None] + factors[1:]
    for bm in (16, 24):
        got = kops.mttkrp_bucketed(bk, fac, num_rows=40, use_pallas=True,
                                   tile=KernelTile(block_m=bm))
        want = kops.mttkrp_bucketed(bk, fac, num_rows=40, use_pallas=False)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"block_m={bm}")


@pytest.mark.parametrize("r", [10, 5])
def test_rank_not_multiple_of_block_r(r):
    """R that doesn't divide block_r: ops pads the factors' rank axis and
    slices the result back."""
    st, factors = _mk(jax.random.PRNGKey(11), (32, 16, 8), 200, r,
                      jnp.float32)
    tile = KernelTile(block_m=64, block_r=32)
    got = kops.tttp_values(st, factors, use_pallas=True, tile=tile)
    want = kref.tttp_ref(st.values * st.mask, st.indices, factors)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    bk = bucketize(st, 0, block_rows=8)
    fac = [None] + factors[1:]
    got = kops.mttkrp_bucketed(bk, fac, num_rows=32, use_pallas=True,
                               tile=tile)
    want = kops.mttkrp_bucketed(bk, fac, num_rows=32, use_pallas=False)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_single_factor_mttkrp_matrix_case():
    """2-D tensor: the Hadamard chain degenerates to ONE other factor."""
    st, factors = _mk(jax.random.PRNGKey(12), (128, 8), 200, 8, jnp.float32)
    bk = bucketize(st, 0, block_rows=8)
    fac = [None, factors[1]]
    got = kops.mttkrp_bucketed(bk, fac, num_rows=128, use_pallas=True)
    dense = st.todense()
    want = jnp.einsum("ij,jr->ir", dense, factors[1])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# §13's documented bf16 bound: bf16 Hadamard chain, fp32 MXU accumulation
BF16_TOL = dict(rtol=6e-2, atol=6e-2)


def test_mttkrp_bf16_accumulates_fp32():
    st, factors = _mk(jax.random.PRNGKey(13), (64, 32, 16), 500, 16,
                      jnp.bfloat16)
    bk = bucketize(st, 0, block_rows=8)
    fac = [None] + factors[1:]
    got = kops.mttkrp_bucketed(bk, fac, num_rows=64, use_pallas=True)
    assert got.dtype == jnp.bfloat16
    f32 = [None] + [f.astype(jnp.float32) for f in factors[1:]]
    bk32 = bucketize(st.astype(jnp.float32), 0, block_rows=8)
    want = kops.mttkrp_bucketed(bk32, f32, num_rows=64, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **BF16_TOL)


def test_cg_matvec_bf16_accumulates_fp32():
    key = jax.random.PRNGKey(14)
    st, factors = _mk(key, (64, 32, 16), 500, 16, jnp.bfloat16)
    omega = st.with_values(jnp.ones_like(st.values))
    bk = bucketize(omega, 0, block_rows=8)
    fac = [None] + factors[1:]
    x = jax.random.normal(key, (64, 16), jnp.bfloat16)
    got = kops.cg_matvec_bucketed(bk, fac, x, num_rows=64, use_pallas=True)
    assert got.dtype == jnp.bfloat16
    f32 = [None] + [f.astype(jnp.float32) for f in factors[1:]]
    bk32 = bucketize(omega.astype(jnp.float32), 0, block_rows=8)
    want = kops.cg_matvec_bucketed(bk32, f32, x.astype(jnp.float32),
                                   num_rows=64, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **BF16_TOL)


# ---------------------------------------------------------------------------
# dispatch rule (kops.route): the TPU branch is steered by patching the
# probed platform; nothing here needs a chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env,want", [(None, "xla"), ("0", "xla"),
                                      ("1", "pallas")])
def test_route_on_cpu(monkeypatch, env, want):
    monkeypatch.setattr(kops, "_platform", lambda: "cpu")
    if env is None:
        monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    else:
        monkeypatch.setenv("REPRO_USE_PALLAS", env)
    assert all(kops.route(f) == want for f in ("tttp", "mttkrp", "cg_matvec"))
    assert kops.route("tttp", use_pallas=True) == "pallas"
    assert kops.route("tttp", use_pallas=False) == "xla"


@pytest.mark.parametrize("family", sorted(kops.TPU_REFUSED))
def test_route_on_tpu_refused_family(monkeypatch, family):
    """A kernel the TPU compiler refuses takes XLA by default; asking for it
    raises with the compiler's reason instead of interpreting it."""
    monkeypatch.setattr(kops, "_platform", lambda: "tpu")
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    assert kops.route(family) == "xla"
    with pytest.raises(RuntimeError, match="Mosaic"):
        kops.route(family, use_pallas=True)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    with pytest.raises(RuntimeError, match="refuses"):
        kops.route(family)


def test_route_on_tpu_compiling_family(monkeypatch):
    monkeypatch.setattr(kops, "_platform", lambda: "tpu")
    monkeypatch.setattr(kops, "TPU_REFUSED", {})
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    assert kops.route("tttp") == "pallas"
    assert kops.route("tttp", use_pallas=False) == "xla"
    assert not kops._interpret()


def test_no_interpret_mode_on_tpu(monkeypatch):
    """On a TPU an explicit Pallas request for a refused kernel raises
    before any kernel is built: interpret mode is never reached."""
    monkeypatch.setattr(kops, "_platform", lambda: "tpu")
    st, factors = _mk(jax.random.PRNGKey(5), (13, 9, 7), 50, 8, jnp.float32)
    with pytest.raises(RuntimeError, match="tttp"):
        kops.tttp_values(st, factors, use_pallas=True)
    got = kops.tttp_values(st, factors)
    want = kops.tttp_values(st, factors, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
