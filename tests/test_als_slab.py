"""The ALS Gram operator in row-slab form (``als.gram_slabs``): its matvec
and right-hand side against the COO ``gram_matvec`` and ``mttkrp``, under
a data-axis ``shard_map`` too; the shape rule that picks it per mode, and
the counters that say which path each mode took."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.completion import als_sweep, als_sweep_stats
from repro.core.completion import als
from repro.core.distributed import AxisCtx
from repro.core.sparse_tensor import SparseTensor
from repro.sparse.ops import mttkrp

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rows_tensor(key, shape, counts, invalid=37):
    """A tensor whose mode-0 row ``i`` holds ``counts[i]`` nonzeros (other
    coordinates uniform), shuffled, then ``invalid`` padding entries with
    in-range coordinates and nonzero values that the mask must hide."""
    ks = jax.random.split(key, len(shape) + 3)
    rows = np.repeat(np.arange(len(counts)), counts)
    m = rows.size
    cols = [jnp.asarray(rows, jnp.int32)] + [
        jax.random.randint(ks[d], (m,), 0, s, jnp.int32)
        for d, s in enumerate(shape) if d > 0]
    perm = jax.random.permutation(ks[-1], m)
    idx = jnp.stack(cols, 1)[perm]
    junk = jnp.stack([jax.random.randint(jax.random.fold_in(ks[-2], d),
                                         (invalid,), 0, s, jnp.int32)
                      for d, s in enumerate(shape)], 1)
    idx = jnp.concatenate([idx, junk])
    vals = jax.random.normal(ks[-3], (m + invalid,))
    valid = jnp.arange(m + invalid) < m
    return SparseTensor(idx, vals, valid, tuple(shape), m)


def _factors(key, shape, r):
    return [jax.random.normal(jax.random.fold_in(key, d), (s, r)) / r ** 0.5
            for d, s in enumerate(shape)]


# mode-0 rows of K = 128 slots: empty, singletons, one exact slab, one
# over three slabs
_COUNTS = [0, 1, 5, 0, 128, 1, 417, 60, 0, 129, 1, 3]
# mode-0 rows of K = 8 (7.3 entries a row with the 37 invalid ones): empty,
# singletons, K - 1, K, K + 1, three and four slabs
_NARROW_COUNTS = [0, 1, 7, 8, 9, 0, 1, 26, 3, 0, 2, 17, 0, 1, 5, 0]
# (mode-0 counts, their slab width, slots a build step) of each layout
_WIDTHS = {"wide": (_COUNTS, 128, 4 * 128), "narrow": (_NARROW_COUNTS, 8, 64)}


def _slots(ops):
    """``ops.w`` as one row of ``K`` slots per slab, in slab order."""
    w = np.asarray(ops.w)
    if ops.slot_axis == -2:
        w = w.swapaxes(-1, -2)
    return w.reshape(ops.row.size, ops.width)


@pytest.mark.parametrize("layout", sorted(_WIDTHS))
@pytest.mark.parametrize("order,rank", [(3, 10), (3, 32), (4, 10), (4, 32)])
def test_slab_operator_matches_coo(order, rank, layout, monkeypatch):
    """Matvec and right-hand side on the slab operator equal the COO
    ``gram_matvec`` and ``mttkrp`` to float32 rounding, for every mode,
    with weights other than one, and built over several build steps, at
    128 slots a slab and at narrower widths (8 to 32, one per mode)."""
    counts, width, build_slots = _WIDTHS[layout]
    monkeypatch.setattr(als, "BUILD_SLOTS", build_slots)
    key = jax.random.PRNGKey(order * 100 + rank)
    shape = (len(counts), 9, 7, 5)[:order]
    st = _rows_tensor(key, shape, counts)
    omega = st.with_values(
        jax.random.uniform(jax.random.fold_in(key, 7), (st.cap,),
                           minval=0.5, maxval=2.0))
    fs = _factors(key, shape, rank)
    assert als.slab_width(omega, 0) == width
    for mode in range(order):
        ops = als.gram_slabs(st, omega, fs, mode)
        assert ops.width == als.slab_width(omega, mode)
        assert ops.z.shape[0] > 1 and ops.z.shape[1] == rank
        assert np.all(np.diff(np.asarray(ops.row)) >= 0)
        x = jax.random.normal(jax.random.fold_in(key, 50 + mode),
                              (shape[mode], rank))
        got = als.slab_matvec(ops, x, 0.3)
        want = als.gram_matvec(omega, fs, mode, x, 0.3)
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * scale)
        others = [None if e == mode else f for e, f in enumerate(fs)]
        got_b = als.slab_rhs(ops, shape[mode])
        want_b = mttkrp(st, others, mode)
        scale = float(jnp.max(jnp.abs(want_b)))
        np.testing.assert_allclose(got_b, want_b, rtol=1e-5,
                                   atol=1e-5 * scale)


@pytest.mark.parametrize("layout", sorted(_WIDTHS))
def test_slab_rows_hold_one_row_each(layout):
    """Every slab's used slots belong to the slab's row, and each row
    holds exactly its nonzeros' weights: the layout loses and invents
    nothing."""
    counts, width, _ = _WIDTHS[layout]
    key = jax.random.PRNGKey(3)
    shape = (len(counts), 6, 4)
    st = _rows_tensor(key, shape, counts)
    omega = st.with_values(jnp.ones_like(st.values))
    ops = als.gram_slabs(st, omega, _factors(key, shape, 4), 0)
    assert ops.width == width
    row = np.asarray(ops.row)
    w = _slots(ops)
    per_row = np.zeros(len(counts) + 1)
    np.add.at(per_row, row, w.sum(1))
    np.testing.assert_array_equal(per_row[:-1], counts)
    assert per_row[-1] == 0            # the sentinel row holds no weight
    used = (w != 0).sum(1)
    want_slabs = sum(-(-c // width) for c in counts)
    assert (row < len(counts)).sum() == want_slabs
    assert used[row == len(counts)].sum() == 0


def test_shape_rule_and_counters():
    """A mode with fewer than SLAB nonzeros a row on average takes slabs
    of the least power of two from 8 that holds its average, and one with
    fewer nonzeros than rows keeps the COO matvec; the ``als/gram/*``
    counters say which path each mode took, the ``width`` gauge at what
    width, and any context, H-slicing or planner path the slab operator
    does not serve keeps every mode on COO."""
    key = jax.random.PRNGKey(4)
    shape = (8, 200, 6, 4000)                  # 250, 10, 333, 0.5 a row
    st = SparseTensor.random(key, shape, 2000)
    omega = st.with_values(jnp.ones_like(st.values))
    fs = tuple(_factors(key, shape, 4))
    assert [als.slab_mode(omega, d) for d in range(4)] == [True, True, True,
                                                           False]
    assert [als.slab_width(omega, d) for d in range(3)] == [128, 16, 128]
    assert not als.slab_mode(omega, 0, ctx=AxisCtx(model="model"))
    assert not als.slab_mode(omega, 0, h_slices=2)
    assert not als.slab_mode(omega, 0, mttkrp_path="all_at_once")

    def counters(**kw):
        obs.get_registry().reset()
        obs.enable()
        try:
            jax.jit(lambda s, o, f: tuple(als_sweep(
                s, o, list(f), 1e-3, cg_iters=4, **kw))).lower(st, omega, fs)
            summary = obs.get_registry().summary()
        finally:
            obs.disable()
            obs.get_registry().reset()
        return ({k: v for k, v in summary["counters"].items()
                 if k.startswith("als/gram/")}, summary["gauges"])

    got, gauges = counters()
    assert got == {"als/gram/slab": 3.0, "als/gram/coo": 1.0}
    assert gauges["als/gram/mode_0/cap"] == st.cap
    assert gauges["als/gram/mode_0/slots"] >= st.cap + 8 * (als.SLAB - 1)
    assert gauges["als/gram/mode_1/slots"] >= st.cap + 200 * (16 - 1)
    assert [gauges[f"als/gram/mode_{d}/width"] for d in range(3)] == [
        128, 16, 128]
    assert gauges["als/gram/mode_3/rows"] == 4000
    assert "als/gram/mode_3/slots" not in gauges
    assert "als/gram/mode_3/width" not in gauges
    for kw in ({"h_slices": 2}, {"mttkrp_path": "all_at_once"}):
        assert counters(**kw)[0] == {"als/gram/coo": 4.0}


@pytest.mark.parametrize("shape,widths", [
    ((12, 10, 8), [128, 128, 128]),            # 250 to 375 a row
    ((300, 250, 8), [16, 16, 128]),            # 10, 12 and 375 a row
])
def test_cg_steps_match_coo_path(shape, widths):
    """``als_sweep_stats`` on the slab path runs within one CG step per
    mode of the COO path (kept by an explicit ``mttkrp_path``) from the
    same factors, and lands on the same factors to CG's tolerance, at 128
    slots a slab and at narrower widths."""
    key = jax.random.PRNGKey(6)
    st = SparseTensor.random(key, shape, 3000)
    true = _factors(jax.random.PRNGKey(60), shape, 3)
    st = st.with_values(jnp.sum(true[0][st.indices[:, 0]]
                                * true[1][st.indices[:, 1]]
                                * true[2][st.indices[:, 2]], 1))
    omega = st.with_values(jnp.ones_like(st.values))
    fs = tuple(_factors(key, shape, 6))
    assert all(als.slab_mode(omega, d) for d in range(3))
    assert [als.slab_width(omega, d) for d in range(3)] == widths

    def run(path):
        f = jax.jit(lambda s, o, f: als_sweep_stats(
            s, o, list(f), 1e-3, cg_tol=1e-4, cg_iters=20, mttkrp_path=path))
        out, steps = f(st, omega, fs)
        return out, np.asarray(steps)

    slab, s_steps = run(None)
    coo, c_steps = run("all_at_once")
    assert np.all(np.abs(s_steps - c_steps) <= 1), (s_steps, c_steps)
    for a, b in zip(slab, coo):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


_SHARDED = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.join(sys.argv[1], "src"))
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro import obs
    from repro.core.completion import als, als_sweep
    from repro.core.distributed import AxisCtx, LOCAL, make_mesh
    from repro.core.sparse_tensor import SparseTensor
    from repro.data.synthetic import shuffle_and_pad
    from repro.sparse.ops import mttkrp

    mesh = make_mesh((4,), ("data",))
    ctx = AxisCtx(data="data")
    key = jax.random.PRNGKey(0)
    r = 5
    rep = P(None, None)

    def problem(shape):
        st = SparseTensor.random(key, shape, 8000, cap=8192)
        st = shuffle_and_pad(st, key, 4)
        omega = st.with_values(jnp.ones_like(st.values))
        fs = [jax.random.normal(jax.random.fold_in(key, d), (s, r)) / r ** 0.5
              for d, s in enumerate(shape)]
        spec = SparseTensor(P("data", None), P("data"), P("data"), st.shape,
                            st.nnz, None)
        return st, omega, fs, spec

    def shard(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    # each shard holds 2,048 entries: 171 to 256 a row (K = 128) in the
    # first tensor, 5.1 a row (K = 8) in the last mode of the second
    widths = []
    for shape in ((12, 10, 8), (12, 10, 400)):
        st, omega, fs, spec = problem(shape)
        for mode in range(3):
            x = jax.random.normal(jax.random.fold_in(key, 9),
                                  (shape[mode], r))
            def both(s, o, f, x):
                ops = als.gram_slabs(s, o, list(f), mode)
                widths.append(ops.width)
                return (als.slab_matvec(ops, x, 0.1, ctx),
                        als.slab_rhs(ops, shape[mode], ctx))
            got_y, got_b = shard(both, (spec, spec, (rep,) * 3, rep),
                                 (rep, rep))(st, omega, tuple(fs), x)
            want_y = als.gram_matvec(omega, fs, mode, x, 0.1)
            want_b = mttkrp(st, [None if e == mode else f
                                 for e, f in enumerate(fs)], mode)
            for g, w in ((got_y, want_y), (got_b, want_b)):
                scale = float(jnp.max(jnp.abs(w)))
                np.testing.assert_allclose(g, w, rtol=1e-5,
                                           atol=1e-5 * scale)
    assert widths == [128] * 5 + [8], widths
    print("SLAB-OPERATOR-SHARDED-OK")

    obs.enable()
    for shape in ((12, 10, 8), (12, 10, 400)):
        st, omega, fs, spec = problem(shape)
        obs.get_registry().reset()
        sweep = shard(lambda s, o, f: tuple(als_sweep(s, o, list(f), 1e-3,
                                                      cg_iters=12, ctx=ctx)),
                      (spec, spec, (rep,) * 3), (rep,) * 3)
        got = sweep(st, omega, tuple(fs))
        counters = obs.get_registry().summary()["counters"]
        assert counters.get("als/gram/slab") == 3.0, counters
        want = als_sweep(st, omega, fs, 1e-3, cg_iters=12,
                         mttkrp_path="all_at_once")
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3)
    print("SLAB-SWEEP-SHARDED-OK")
""")


def test_slab_operator_under_data_shard_map():
    """Under a data-axis ``shard_map`` each shard builds its own operator
    over its nonzeros, at 128 slots a slab and at 8; the psum over the
    data axis makes the matvec and the right-hand side the COO ones of the
    whole tensor, and the sharded sweep (every mode on slabs) lands on the
    local COO sweep."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _SHARDED, _ROOT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SLAB-OPERATOR-SHARDED-OK" in out.stdout
    assert "SLAB-SWEEP-SHARDED-OK" in out.stdout
