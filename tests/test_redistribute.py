"""Tests for ``repro.sparse.redistribute`` (paper Fig. 4): distributed
transpose with shard-boundary rebalancing, order-preserving reshape, and the
butterfly sparse all-reduce on ≥4 forced host devices (subprocess, per the
single-device harness contract)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse_tensor import SparseTensor
from repro.sparse import redistribute


def _random_st(key, shape=(12, 10, 8), nnz=200, cap=256):
    return SparseTensor.random(key, shape, nnz, cap=cap)


def test_transpose_distributed_matches_dense():
    st = _random_st(jax.random.PRNGKey(0))
    perm = (2, 0, 1)
    out = redistribute.transpose_distributed(st, perm)
    np.testing.assert_allclose(np.asarray(out.todense()),
                               np.asarray(jnp.transpose(st.todense(), perm)),
                               rtol=1e-6, atol=1e-6)


def test_transpose_distributed_resorts_by_new_leading_mode():
    """The global re-sort is the redistribution step: after transposition
    entries are sorted by the NEW mode 0 (shard-boundary rebalancing), with
    padding pushed to the end."""
    st = _random_st(jax.random.PRNGKey(1))
    out = redistribute.transpose_distributed(st, (1, 2, 0))
    assert out.sorted_mode == 0
    rows = np.asarray(out.indices[:, 0])
    valid = np.asarray(out.valid)
    nnz = int(valid.sum())
    # all valid entries first (padding rebalanced to the tail) ...
    assert valid[:nnz].all() and not valid[nnz:].any()
    # ... and sorted by the new leading mode
    assert (np.diff(rows[:nnz]) >= 0).all()


def test_transpose_distributed_no_resort_keeps_order():
    st = _random_st(jax.random.PRNGKey(2))
    out = redistribute.transpose_distributed(st, (1, 0, 2), resort=False)
    assert out.sorted_mode is None
    np.testing.assert_array_equal(np.asarray(out.indices[:, 0]),
                                  np.asarray(st.indices[:, 1]))


def test_reshape_distributed_preserves_global_order():
    from repro.core.utils import lex_sort_perm
    st = _random_st(jax.random.PRNGKey(3))
    p = lex_sort_perm(st.indices, st.valid, range(st.ndim))
    st = SparseTensor(st.indices[p], st.values[p], st.valid[p], st.shape,
                      st.nnz, sorted_mode=0)
    out = redistribute.reshape_distributed(st, (12 * 10, 8))
    assert out.sorted_mode == 0
    rows = np.asarray(out.indices[:, 0])[np.asarray(out.valid)]
    assert (np.diff(rows) >= 0).all()   # row-major order really is preserved
    np.testing.assert_allclose(
        np.asarray(out.todense()),
        np.asarray(st.todense().reshape(12 * 10, 8)), rtol=1e-6, atol=1e-6)


_DIST_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from repro.core.distributed import make_mesh
    from jax.sharding import PartitionSpec as P

    from repro.core.sparse_tensor import SparseTensor
    from repro.core.distributed import sparse_allreduce_butterfly
    from repro.sparse import redistribute
    from repro.data.synthetic import shuffle_and_pad

    mesh = make_mesh((4,), ("data",))
    key = jax.random.PRNGKey(0)

    # 1) sharded transpose_distributed == local dense transpose (the global
    #    sort is XLA's distributed sort over the sharded arrays)
    st = shuffle_and_pad(SparseTensor.random(key, (16, 12, 8), 500, cap=512),
                         key, 4)
    st = redistribute.shard_nonzeros(st, mesh, "data")
    out = jax.jit(lambda s: redistribute.transpose_distributed(s, (2, 1, 0)))(st)
    np.testing.assert_allclose(
        np.asarray(out.todense()),
        np.asarray(jnp.transpose(st.todense(), (2, 1, 0))),
        rtol=1e-5, atol=1e-5)
    rows = np.asarray(out.indices[:, 0]); valid = np.asarray(out.valid)
    nnz = int(valid.sum())
    assert valid[:nnz].all() and (np.diff(rows[:nnz]) >= 0).all()
    print("transpose-dist-ok")

    # 2) butterfly sparse all-reduce over 4 devices (power-of-two ranks,
    #    device-dependent patterns)
    blocks = [SparseTensor.random(jax.random.fold_in(key, i), (16, 8), 30,
                                  cap=32) for i in range(4)]
    idx = jnp.stack([b.indices for b in blocks])
    vals = jnp.stack([b.values for b in blocks])
    valid = jnp.stack([b.valid for b in blocks])

    def d_butterfly(idx, vals, valid):
        local = SparseTensor(idx[0], vals[0], valid[0], (16, 8), None)
        return sparse_allreduce_butterfly(local, "data").todense()
    got = jax.jit(shard_map(d_butterfly, mesh=mesh,
                            in_specs=(P("data"), P("data"), P("data")),
                            out_specs=P("data"), check_vma=False))(
        idx, vals, valid)
    want = np.asarray(sum(b.todense() for b in blocks))
    got = np.asarray(got).reshape(4, 16, 8)
    for d in range(4):
        np.testing.assert_allclose(got[d], want, rtol=1e-5, atol=1e-5)
    print("butterfly4-ok")
    print("REDIST-DIST-OK")
""")


@pytest.mark.slow
def test_redistribute_distributed_subprocess(tmp_path):
    """Sharded transpose + 4-device butterfly all-reduce (forced host
    devices; see test_distributed.py for the subprocess rationale)."""
    script = tmp_path / "redist_check.py"
    script.write_text(_DIST_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "REDIST-DIST-OK" in out.stdout, out.stdout + "\n---\n" + out.stderr
