"""The program's named scopes and CG step counter as ``scopes.py`` reads
them: the counter against the reference's CG steps on the same inputs (CPU,
small), the scope table on hand-made operations, and on a trace recorded on
a TPU v5e with its ``op_names`` map (``scopes.py --small --out``)."""
import json

import jax
import numpy as np
import pytest

import harness
import reference
import scopes
import tracecut
from conftest import HERE
from tiny import tiny_bench

DATA = HERE / "data"


@pytest.mark.parametrize("seed", (3, 4))
def test_cg_steps_equal_the_references(tmp_path, seed):
    bench = harness.load_benchmark(tiny_bench(tmp_path))
    spec = harness.resolve("function.als", bench)
    devs = harness.devices_for(1, require_tpu=False)
    key = harness.seed_key(seed)
    idx, vals = spec.generator.generate(jax.random.fold_in(key, 0),
                                        spec.cfg, spec.cfg["nnz_per_chip"])
    session = spec.solver.Session(spec.cfg, spec.traffic, devs, idx, vals,
                                  jax.random.fold_in(key, 1),
                                  jax.random.fold_in(key, 2))
    compiled = scopes.compile_stats(session)
    f_in = session.state0
    f_out, steps = compiled(session.st, session.omega, f_in)
    # the same factors as the sweep the benchmark times
    for a, b in zip(session.step(f_in), f_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    got = reference.check_sweep(
        idx, vals, jax.device_get(f_in), jax.device_get(f_out),
        session.lam, session.cg_tol, session.cg_iters,
        spec.limits["settled_margin"])
    assert np.asarray(steps).tolist() == got["cg_steps"]


NAMES = {"fusion.1": "jit(f)/mode_0/rhs/mttkrp/scatter-add",
         "fusion.2": "jit(f)/mode_0/while/body/matvec/tttp/reduce_sum",
         "fusion.3": "jit(f)/mode_1/while/body/matvec/mttkrp/psum/psum",
         "fusion.4": "jit(f)/mode_1/while/body/cg_update/add",
         "fusion.5": "jit(f)/mode_1/while/body/matvec/add",
         "fusion.6": "jit(f)/mode_2/stack",
         "fusion.7": "jit(f)/tttp/mul"}


def test_table_puts_each_operation_under_its_innermost_scope():
    secs = {"fusion.1 f32[9,4]": 1.0, "fusion.2 f32[80]": 2.0,
            "fusion.3 f32[9,4]": 4.0, "fusion.4 f32[9,4]": 8.0,
            "fusion.5 f32[9,4]": 16.0, "copy.7 f32[9,4]": 32.0,
            "fusion.6 s32[3]": 64.0, "fusion.7 f32[80]": 128.0}
    assert scopes.table(secs, NAMES) == {
        "mode_0/mttkrp": 1.0, "mode_0/tttp": 2.0, "mode_1/psum": 4.0,
        "mode_1/cg_update": 8.0, "mode_1/matvec": 16.0, "unscoped": 32.0,
        "mode_2": 64.0, "tttp": 128.0}
    assert scopes.under(secs, NAMES, "mttkrp") == 5.0
    assert scopes.under(secs, NAMES, "tttp", "mttkrp") == 135.0
    assert scopes.under(secs, NAMES, "matvec") == 22.0
    assert scopes.under(secs, NAMES, "mode_1") == 28.0


def test_op_names_reads_instruction_metadata():
    text = ('  %fusion.1 = f32[9,4]{1,0} fusion(%p), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(f)/mode_0/rhs/mttkrp/mul" '
            'stack_frame_id=3}\n'
            '  ROOT %copy.2 = f32[9,4]{1,0} copy(%fusion.1)\n')
    assert scopes.op_names(text) == {"fusion.1": "jit(f)/mode_0/rhs/mttkrp/mul"}


def test_recorded_scope_table_sums_to_busy_time():
    path = str(DATA / f"{scopes.FIXTURE}.xplane.pb")
    names = json.loads((DATA / f"{scopes.FIXTURE}.op_names.json")
                       .read_text())
    busy = tracecut.reduce(path)["busy_s"]
    secs = scopes.op_seconds(path)
    table = scopes.table(secs, names)
    assert sum(table.values()) == pytest.approx(busy, rel=0.01)
    assert table.get(scopes.UNSCOPED, 0.0) < 0.02 * busy
    kernels = scopes.under(secs, names, "tttp", "mttkrp")
    assert 0 < kernels <= busy * (1 + 1e-9)
    # every mode of the order-3 sweep, each with its kernels and CG updates
    assert {f"mode_{d}/{s}" for d in range(3)
            for s in ("tttp", "mttkrp", "cg_update")} <= set(table)
    assert not any(k.startswith("mode_3") for k in table)
    modes = sum(scopes.under(secs, names, f"mode_{d}") for d in range(3))
    assert modes == pytest.approx(busy - table.get(scopes.UNSCOPED, 0.0),
                                  rel=0.01)


def test_recorded_readings_split_the_sweep_by_phase():
    path = str(DATA / f"{scopes.FIXTURE}.xplane.pb")
    names = json.loads((DATA / f"{scopes.FIXTURE}.op_names.json")
                       .read_text())
    busy = tracecut.reduce(path)["busy_s"]
    secs = scopes.op_seconds(path)
    # the fixture's window holds one sweep (control.py's size: 40,000
    # nonzeros, rank 10); its CG steps are not recorded, nine a mode given
    got = scopes.readings(secs, names, busy, [[9, 9, 9]], nnz=40000,
                          rank=10, device_kind="TPU v5 lite")
    phases = 1e-3 * (got["matvec_ms_per_sweep"] + got["rhs_ms_per_sweep"])
    cg = scopes.under(secs, names, "cg_update")
    assert phases + cg == pytest.approx(
        busy * (1 - got["unscoped_pct"] / 100), rel=0.01)
    assert got["matvec_ms_per_sweep"] > got["rhs_ms_per_sweep"] > 0
    assert got["cg_steps_per_sweep"] == 27
    assert 0 < got["kernel_roofline_pct"] < 100
