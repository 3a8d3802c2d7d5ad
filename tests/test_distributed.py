"""Distributed-equivalence tests. These need multiple XLA host devices, so
they run in a SUBPROCESS with XLA_FLAGS set (the main test process keeps the
single-device view per the harness contract)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from repro.core.distributed import make_mesh
    from jax.sharding import PartitionSpec as P

    from repro.core.sparse_tensor import SparseTensor
    from repro.core.completion import als_sweep, sgd_sweep
    from repro.core.distributed import (AxisCtx, LOCAL,
                                        sparse_allreduce_butterfly,
                                        tttp_ctx, mttkrp_ctx)
    from repro.data.synthetic import shuffle_and_pad
    from repro.optim.compression import compressed_psum, ef_state_init

    mesh = make_mesh((4, 2), ("data", "model"))
    ctx = AxisCtx(data="data", model="model")

    key = jax.random.PRNGKey(0)
    I, J, K, R, m = 32, 24, 16, 8, 2000
    st = SparseTensor.random(key, (I, J, K), m, cap=2048)
    st = shuffle_and_pad(st, key, 4)
    omega = st.with_values(jnp.ones_like(st.values))
    ks = jax.random.split(key, 3)
    factors = [jax.random.normal(k, (d, R)) for k, d in
               zip(ks, (I, J, K))]

    st_spec = SparseTensor(P("data", None), P("data"), P("data"),
                           st.shape, st.nnz, None)
    f_spec = P(None, "model")

    # 1) distributed TTTP == local
    def d_tttp(s, fs):
        return tttp_ctx(s, list(fs), ctx).values
    got = jax.jit(shard_map(d_tttp, mesh=mesh,
                            in_specs=(st_spec, (f_spec,) * 3),
                            out_specs=P("data"), check_vma=False))(
        st, tuple(factors))
    want = tttp_ctx(st, factors, LOCAL).values
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("TTTP-dist-ok")

    # 2) distributed MTTKRP == local
    def d_mttkrp(s, fs):
        return mttkrp_ctx(s, [None, fs[1], fs[2]], 0, ctx)
    got = jax.jit(shard_map(d_mttkrp, mesh=mesh,
                            in_specs=(st_spec, (f_spec,) * 3),
                            out_specs=P(None, "model"), check_vma=False))(
        st, tuple(factors))
    want = mttkrp_ctx(st, [None, factors[1], factors[2]], 0, LOCAL)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("MTTKRP-dist-ok")

    # 3) full distributed ALS sweep == local sweep
    def d_als(s, o, fs):
        return tuple(als_sweep(s, o, list(fs), 1e-6, cg_iters=12, ctx=ctx))
    got = jax.jit(shard_map(d_als, mesh=mesh,
                            in_specs=(st_spec, st_spec, (f_spec,) * 3),
                            out_specs=(f_spec,) * 3, check_vma=False))(
        st, omega, tuple(factors))
    want = als_sweep(st, omega, list(factors), 1e-6, cg_iters=12, ctx=LOCAL)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-3, atol=5e-3)
    print("ALS-dist-ok")

    # 3b) planner-routed weighted Gram matvec (cg_matvec family) under data
    #     AND model sharding == local: dispatch inserts the inter-half
    #     psum(model) and the output psum(data)
    from repro.core.completion.als import gram_matvec
    x0 = factors[0]
    def d_gram(s, fs, x):
        return gram_matvec(s, list(fs), 0, x, lam=1e-6, ctx=ctx,
                           matvec_path="auto")
    got = jax.jit(shard_map(d_gram, mesh=mesh,
                            in_specs=(st_spec, (f_spec,) * 3, f_spec),
                            out_specs=P(None, "model"), check_vma=False))(
        omega, tuple(factors), x0)
    want = gram_matvec(omega, factors, 0, x0, lam=1e-6, ctx=LOCAL,
                       matvec_path="auto")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    print("GRAM-planner-dist-ok")

    # 4) butterfly sparse all-reduce == sum of per-shard blocks
    blocks = [SparseTensor.random(jax.random.fold_in(key, i), (32, 8), 40,
                                  cap=64) for i in range(8)]
    idx = jnp.stack([b.indices for b in blocks])
    vals = jnp.stack([b.values for b in blocks])
    valid = jnp.stack([b.valid for b in blocks])

    def d_butterfly(idx, vals, valid):
        local = SparseTensor(idx[0], vals[0], valid[0], (32, 8), None)
        out = sparse_allreduce_butterfly(local, "x")
        return out.todense()
    mesh1 = make_mesh((8,), ("x",))
    got = jax.jit(shard_map(d_butterfly, mesh=mesh1,
                            in_specs=(P("x"), P("x"), P("x")),
                            out_specs=P("x"), check_vma=False))(
        idx, vals, valid)
    want = np.asarray(sum(b.todense() for b in blocks))
    got0 = np.asarray(got).reshape(8, 32, 8)
    for d in range(8):   # every device ends with the full reduced block
        np.testing.assert_allclose(got0[d], want, rtol=1e-5, atol=1e-5)
    print("butterfly-ok")

    # 5) error-feedback int8 compressed psum ~= exact psum
    g = jax.random.normal(key, (8, 64))
    def d_comp(g):
        out, err = compressed_psum(g[0], jnp.zeros_like(g[0]), "x")
        return out
    got = jax.jit(shard_map(d_comp, mesh=mesh1, in_specs=P("x"),
                            out_specs=P("x"), check_vma=False))(g)
    want = g.sum(0)
    rel = float(jnp.max(jnp.abs(got[:64] - want)) /
                (jnp.max(jnp.abs(want)) + 1e-9))
    assert rel < 0.1, rel
    print("compressed-psum-ok")

    print("ALL-DIST-OK")
""")


@pytest.mark.slow
def test_distributed_equivalence_subprocess(tmp_path):
    script = tmp_path / "dist_check.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    # force the host (CPU) platform: the XLA_FLAGS device-count override
    # applies only to it, whatever accelerator the machine has
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "ALL-DIST-OK" in out.stdout, out.stdout + "\n---\n" + out.stderr


_ROWSHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from repro.core.distributed import make_mesh
    from jax.sharding import PartitionSpec as P
    from repro.core.sparse_tensor import SparseTensor
    from repro.core.distributed import (AxisCtx, multilinear_rowsharded,
                                        mttkrp_rowsharded)
    from repro.core.tttp import multilinear_values
    from repro.sparse import ops as sops
    from repro.data.synthetic import shuffle_and_pad

    mesh = make_mesh((8,), ("data",))
    ctx = AxisCtx(data="data", model=None)
    key = jax.random.PRNGKey(0)
    I, J, K, R, m = 64, 48, 32, 8, 2000
    st = shuffle_and_pad(SparseTensor.random(key, (I, J, K), m, cap=2048),
                         key, 8)
    ks = jax.random.split(key, 3)
    factors = [jax.random.normal(k, (d, R)) for k, d in zip(ks, (I, J, K))]
    st_spec = SparseTensor(P("data", None), P("data"), P("data"), st.shape,
                           st.nnz, None)
    f_spec = P("data", None)  # the paper's Fig.2 row distribution

    got = jax.jit(shard_map(
        lambda s, fs: multilinear_rowsharded(s, list(fs), ctx, h_slices=2),
        mesh=mesh, in_specs=(st_spec, (f_spec,) * 3), out_specs=P("data"),
        check_vma=False))(st, tuple(factors))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(multilinear_values(st, factors)),
                               rtol=1e-4, atol=1e-4)

    got2 = jax.jit(shard_map(
        lambda s, fs: mttkrp_rowsharded(s, list(fs), 0, ctx, h_slices=2),
        mesh=mesh, in_specs=(st_spec, (f_spec,) * 3),
        out_specs=P("data", None), check_vma=False))(st, tuple(factors))
    want2 = sops.mttkrp(st, [None, factors[1], factors[2]], 0)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=1e-4, atol=1e-4)
    print("ROWSHARD-OK")
""")


@pytest.mark.slow
def test_rowsharded_factors_subprocess(tmp_path):
    """Paper Fig. 2 row distribution: H-sliced gathers + reduce-scatter."""
    script = tmp_path / "rowshard_check.py"
    script.write_text(_ROWSHARD_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"    # see test_distributed_equivalence_subprocess
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "ROWSHARD-OK" in out.stdout, out.stdout + "\n---\n" + out.stderr
