"""Compile-only checks against a described TPU v5e (no chip needed).

(a) The jitted ALS and GGN sweeps compile for one chip at the benchmark's
    ``netflix`` deployment (``chipbench/configs/netflix.json``: Netflix
    extents, rank 32, one chip's 1/32 of the ratings; ALS with every mode
    on the row-slab operator, the users' at 8 slots a slab) and fit
    the chip's 16 GiB with 10% headroom, by ``memory_analysis()``. At the
    benchmark's function-10b extents every ALS mode takes the row-slab Gram
    operator and needs no more device bytes than the COO matvec.
(b) Each Pallas kernel family is compiled once for the chip at a geometry
    that fits VMEM. Mosaic refuses all three today (``kernels.ops.
    TPU_REFUSED``), so these are strict xfails: a change that makes a kernel
    compile turns its test red, and must then drop the family from
    ``TPU_REFUSED`` and this xfail.

The topology is described inside a module-scoped fixture only: only one
process at a time may load the TPU library, so nothing here may touch it
while modules are imported or tests collected.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.core import losses as LOSS
from repro.core.completion import als_sweep, ggn_sweep
from repro.core.completion.gauss_newton import GGNState
from repro.core.sparse_tensor import SparseTensor
from repro.kernels import ops as kops
from repro.kernels.cg_matvec import cg_matvec_pallas
from repro.kernels.mttkrp import mttkrp_pallas
from repro.kernels.tile import KernelTile
from repro.kernels.tttp import tttp_pallas
from repro.sparse.ccsr import RowBlockBuckets

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES = 16 * 2 ** 30             # one v5e chip
HEADROOM = 0.9


def _config(name):
    """A benchmark configuration (``chipbench/configs/<name>.json``)."""
    with open(os.path.join(_ROOT, "chipbench", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _als_compile(sharding, shape, m, r, lam, **kw):
    """The jitted ALS sweep compiled for ``sharding``'s chip, the
    ``als/gram/*`` counters its trace bumped and the gauges it set."""
    st = SparseTensor(_sds(sharding, (m, len(shape)), jnp.int32),
                      _sds(sharding, (m,), jnp.float32),
                      _sds(sharding, (m,), jnp.bool_), tuple(shape), m)
    fs = tuple(_sds(sharding, (d, r), jnp.float32) for d in shape)
    fn = jax.jit(lambda s, o, f: tuple(als_sweep(
        s, o, list(f), lam, cg_tol=1e-4, cg_iters=20, **kw)))
    obs.get_registry().reset()
    obs.enable()
    try:
        compiled = fn.lower(st, st, fs).compile()
        summary = obs.get_registry().summary()
    finally:
        obs.disable()
        obs.get_registry().reset()
    return compiled, {k: v for k, v in summary["counters"].items()
                      if k.startswith("als/gram/")}, summary["gauges"]


@pytest.mark.parametrize("solver", ["als", "ggn"])
def test_sweep_fits_one_chip(one_chip, solver):
    cfg = _config("netflix")
    m, shape, r, lam = (cfg["nnz_per_chip"], tuple(cfg["shape"]), cfg["rank"],
                        cfg["lam"])
    if solver == "als":
        compiled, counters, gauges = _als_compile(one_chip, shape, m, r, lam)
        # the user mode's rows are short (about 6.5 nonzeros): slabs of 8
        # slots there, of 128 in the movie and day modes
        assert counters == {"als/gram/slab": 3.0}
        assert [gauges[f"als/gram/mode_{d}/width"] for d in range(3)] == [
            8, 128, 128]
    else:
        st = SparseTensor(_sds(one_chip, (m, 3), jnp.int32),
                          _sds(one_chip, (m,), jnp.float32),
                          _sds(one_chip, (m,), jnp.bool_), shape, m)
        fs = tuple(_sds(one_chip, (d, r), jnp.float32) for d in shape)
        loss = LOSS.LOSSES["poisson_log"]
        fn = jax.jit(lambda s, state: ggn_sweep(
            s, state, loss, lam, cg_tol=1e-4, cg_iters=20))
        compiled = fn.lower(st, GGNState(fs, _sds(one_chip, (),
                                                  jnp.float32))).compile()
    used = _device_bytes(compiled)
    assert used <= HEADROOM * HBM_BYTES, (
        f"{solver} sweep at nnz={m} needs {used / 2 ** 30:.2f} GiB")


def test_als_slab_path_at_function_extents(one_chip):
    """At the function-10b cell's extents (16,384 a mode, rank 10,
    3,139,928 nonzeros) all three modes take the row-slab operator, and
    the sweep needs no more device bytes than the COO matvec, which an
    explicit ``mttkrp_path`` keeps."""
    cfg = _config("function-10b")
    args = (cfg["shape"], cfg["nnz_per_chip"], cfg["rank"], cfg["lam"])
    slab, counters, _ = _als_compile(one_chip, *args)
    assert counters == {"als/gram/slab": 3.0}
    coo, counters, _ = _als_compile(one_chip, *args,
                                    mttkrp_path="all_at_once")
    assert counters == {"als/gram/coo": 3.0}
    assert _device_bytes(slab) <= _device_bytes(coo), (
        _device_bytes(slab) / 2 ** 30, _device_bytes(coo) / 2 ** 30)


def _kernel_case(family, sharding):
    """A VMEM-sized geometry: 1M nonzeros (TTTP) or 256 buckets of 1024
    (bucketed kernels), extents 2048/1024/512, R=128."""
    shape, r = (2048, 1024, 512), 128
    fs = tuple(_sds(sharding, (d, r), jnp.float32) for d in shape)
    if family == "tttp":
        m = 1 << 20
        fn = jax.jit(lambda v, i, f: tttp_pallas(v, i, list(f),
                                                 interpret=False))
        return fn, (_sds(sharding, (m,), jnp.float32),
                    _sds(sharding, (m, 3), jnp.int32), fs)
    nb, cap = shape[0] // 8, 1024
    bk = RowBlockBuckets(_sds(sharding, (nb, cap), jnp.float32),
                         _sds(sharding, (nb, cap, 3), jnp.int32),
                         _sds(sharding, (nb, cap), jnp.int32),
                         _sds(sharding, (nb, cap), jnp.bool_), 0, 8, shape)
    # 8 buckets per grid step keeps the (g, capacity) blocks (8, 128)-tiled
    tile = KernelTile(buckets_per_step=8)
    if family == "mttkrp":
        fn = jax.jit(lambda b, f: mttkrp_pallas(
            b, [None, f[1], f[2]], tile=tile, interpret=False))
        return fn, (bk, fs)
    fn = jax.jit(lambda b, f: cg_matvec_pallas(
        b, [None, f[1], f[2]], f[0], tile=tile, interpret=False))
    return fn, (bk, fs)


@pytest.mark.parametrize("family", [
    pytest.param(f, marks=pytest.mark.xfail(
        strict=True, raises=ValueError, reason=kops.TPU_REFUSED[f]))
    for f in ("tttp", "mttkrp", "cg_matvec")])
def test_pallas_kernel_compiles_for_chip(one_chip, family):
    fn, args = _kernel_case(family, one_chip)
    try:
        fn.lower(*args).compile()
    except ValueError as e:
        # only the refusal TPU_REFUSED names counts as the expected failure
        assert "Shape mismatch in input, indices and output" in str(e), e
        raise
