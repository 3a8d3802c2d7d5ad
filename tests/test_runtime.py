"""Checkpoint/restart, elastic resharding, straggler watchdog, data
pipeline, and the NumPy-style facade."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.api as ctf
from repro.checkpoint.checkpointer import Checkpointer, latest_step, restore, save
from repro.core.sparse_tensor import SparseTensor
from repro.data import synthetic
from repro.runtime.elastic import replan_sparse
from repro.runtime.fault_tolerance import RestartableLoop, StepWatchdog


def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(12.0).reshape(3, 4),
             "b": [jnp.ones((2,)), jnp.zeros((5,), jnp.int32)]}
    save(str(tmp_path), 7, state, metadata={"note": "x"})
    assert latest_step(str(tmp_path)) == 7
    like = jax.tree.map(jnp.zeros_like, state)
    got, manifest = restore(str(tmp_path), 7, like)
    assert manifest["metadata"]["note"] == "x"
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(got)):
        np.testing.assert_allclose(a, b)


def test_checkpoint_gc_keeps_last(tmp_path):
    for s in range(6):
        save(str(tmp_path), s, {"x": jnp.ones(3) * s}, keep_last=2)
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [4, 5]


def test_restart_resume_equivalence(tmp_path):
    """Run with injected failure, restart, final state == uninterrupted."""
    def step(i, state):
        return state + (i + 1)

    loop = RestartableLoop(str(tmp_path / "a"), step, ckpt_every=3)
    state = loop.run(jnp.zeros(2), 10)

    loop2 = RestartableLoop(str(tmp_path / "b"), step, ckpt_every=3)
    with pytest.raises(RuntimeError):
        loop2.run(jnp.zeros(2), 10, fail_at=5)
    loop3 = RestartableLoop(str(tmp_path / "b"), step, ckpt_every=3)
    state2 = loop3.run(jnp.zeros(2), 10)
    np.testing.assert_allclose(state, state2)


def test_corrupt_checkpoint_fallback(tmp_path):
    def step(i, state):
        return state + 1

    loop = RestartableLoop(str(tmp_path), step, ckpt_every=2, keep_last=5)
    with pytest.raises(RuntimeError):
        loop.run(jnp.zeros(1), 10, fail_at=7)
    # corrupt the newest checkpoint's arrays
    newest = max(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    for f in os.listdir(os.path.join(tmp_path, newest)):
        if f.endswith(".npy"):
            os.remove(os.path.join(tmp_path, newest, f))
    loop2 = RestartableLoop(str(tmp_path), step, ckpt_every=2, keep_last=5)
    state = loop2.run(jnp.zeros(1), 10)
    assert float(state[0]) == 10.0


def test_async_checkpointer(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save_async(3, {"w": jnp.ones((4, 4))})
    ck.wait()
    assert ck.latest() == 3


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(threshold=2.0, warmup=3)
    for i in range(8):
        wd.observe(0.1, i)
    wd.observe(1.0, 8)
    assert wd.events and wd.events[-1][0] == 8


def test_elastic_replan_preserves_data():
    key = jax.random.PRNGKey(0)
    st = SparseTensor.random(key, (30, 20, 10), 500)
    total = float(st.sum())
    for shards in (1, 2, 4):
        re = replan_sparse(st, key, None)
        assert abs(float(re.sum()) - total) < 1e-3
        assert int(jnp.sum(re.valid)) == 500


def test_shuffle_and_pad_balances(tmp_path):
    key = jax.random.PRNGKey(1)
    st = SparseTensor.random(key, (64, 64), 1000)
    out = synthetic.shuffle_and_pad(st, key, 8)
    assert out.cap % 8 == 0
    per = np.asarray(out.valid).reshape(8, -1).sum(1)
    assert per.std() < per.mean() * 0.2  # padding spread evenly


def test_function_tensor_low_rank():
    """Karlsson model problem really is low-rank: ALS rank 6 fits well."""
    from repro.core.completion import als_sweep
    key = jax.random.PRNGKey(2)
    st = synthetic.function_tensor(key, (40, 40, 40), 6000)
    omega = st.with_values(jnp.ones_like(st.values))
    fs = [jax.random.normal(jax.random.fold_in(key, d), (40, 6)) * 0.4
          for d in range(3)]
    sweep = jax.jit(lambda s, o, a, b, c: als_sweep(s, o, [a, b, c], 1e-6,
                                                    cg_iters=12))
    for _ in range(12):
        fs = sweep(st, omega, *fs)
    from repro.core.tttp import multilinear_values
    model = multilinear_values(st, fs)
    resid = (st.values - model) * st.mask
    rmse = float(jnp.sqrt(jnp.sum(resid ** 2) / jnp.sum(st.mask)))
    assert rmse < 0.02


def test_netflix_like_statistics():
    st = synthetic.netflix_like(jax.random.PRNGKey(3),
                                (1000, 500, 50), nnz=20000)
    vals = np.asarray(st.masked_values())[np.asarray(st.valid)]
    assert vals.min() >= 1.0 and vals.max() <= 5.0
    assert 2.0 < vals.mean() < 5.0


def test_api_facade_listings():
    """The paper's Listings 1–3 surface works."""
    key = jax.random.PRNGKey(4)
    T = ctf.random_sparse((12, 10, 8), 100, key)
    U = jnp.ones((12, 4))
    V = jnp.ones((10, 4))
    W = jnp.ones((8, 4))
    S = ctf.TTTP(T, [U, V, W])                      # Listing 3
    np.testing.assert_allclose(S.masked_values(),
                               4.0 * T.masked_values(), rtol=1e-6)
    S2 = ctf.TTTP(T, [U, None, W])
    np.testing.assert_allclose(S2.masked_values(),
                               4.0 * T.masked_values(), rtol=1e-6)
    y = ctf.einsum("ijk,jr,kr->ir", T, V, W)        # MTTKRP
    assert y.shape == (12, 4)
    a = ctf.einsum("ijk->i", S)                     # sparse reduction
    assert a.shape == (12,)
    dense = ctf.einsum("ijk,kr->ijr", T, W)         # TTM
    assert dense.shape == (12, 10, 4)


def test_compression_error_feedback_converges():
    """EF-int8: accumulated compressed sums track the true sums."""
    from repro.optim.compression import compressed_psum
    # single-device psum over trivial axis via vmap-style emulation is
    # covered in the distributed subprocess test; here check quantizer error
    # feedback: repeated compression of a constant recovers it on average.
    import jax
    from jax import shard_map
    from repro.core.distributed import make_mesh
    from jax.sharding import PartitionSpec as P
    g = jnp.array([1.234e-3] * 64)
    err = jnp.zeros_like(g)
    mesh = make_mesh((1,), ("x",))
    f = shard_map(lambda gg, ee: compressed_psum(gg, ee, "x"),
                  mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                  check_vma=False)
    acc = jnp.zeros_like(g)
    for _ in range(20):
        out, err = f(g, err)
        acc = acc + out
    np.testing.assert_allclose(acc / 20, g, rtol=5e-2)


def test_resume_prints_step(tmp_path, capsys):
    loop = RestartableLoop(str(tmp_path), lambda i, s: s + 1, ckpt_every=2)
    loop.run(jnp.zeros(1), 4)
    capsys.readouterr()
    state = RestartableLoop(str(tmp_path), lambda i, s: s + 1).run(
        jnp.zeros(1), 6)
    assert float(state[0]) == 6.0
    assert "resumed from checkpoint step 3" in capsys.readouterr().out


def test_resume_raises_on_other_state_structure(tmp_path):
    """Another solver's checkpoint in the directory is not corruption: the
    loop must not fall back to step 0 and silently start over."""
    from repro.checkpoint.checkpointer import StructureMismatch

    RestartableLoop(str(tmp_path), lambda i, s: s).run(
        (jnp.zeros(3), jnp.zeros(2)), 2)
    loop = RestartableLoop(str(tmp_path), lambda i, s: s)
    with pytest.raises(StructureMismatch, match="structure drift"):
        loop.run({"factors": (jnp.zeros(3), jnp.zeros(2)),
                  "damping": jnp.zeros(())}, 2)
