"""Render EXPERIMENTS.md §Dry-run and §Roofline tables from previously committed
dry-run JSON records, and the in-repo perf trajectory.

    PYTHONPATH=src python -m repro.launch.report --dir experiments/dryrun
    PYTHONPATH=src python -m repro.launch.report --perf   # writes PERF.md

``--perf`` builds the named CI dataset, runs the planned MTTKRP / TTTP /
fused CG-matvec eagerly with tracing enabled (populating the planner's
predicted-vs-measured table), profiles the jitted kernels against the
machine roofline (``repro.obs.profile_jitted``), folds in the committed
``BENCH_*.json`` trajectory, and writes it all to ``PERF.md``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List


def load(dir_: str) -> List[Dict]:
    recs = []
    for f in sorted(os.listdir(dir_)):
        if f.endswith(".json"):
            recs.append(json.load(open(os.path.join(dir_, f))))
    return recs


def _fmt_bytes(b):
    return f"{b / 2**30:.2f}"


def _note(r) -> str:
    dom = r["dominant"]
    if r["arch"].startswith("completion/"):
        if dom == "collective":
            return ("psum(model) of TTTP partials dominates; H-slice or "
                    "row-shard factors to shrink payloads")
        return ("gather/segment traffic dominates; fuse via the bucketed "
                "Pallas kernels (no (m,R) intermediates)")
    kinds = r.get("collective_by_kind", {})
    top = max(kinds, key=kinds.get) if kinds else "none"
    if dom == "collective":
        return (f"{top} dominates wire bytes; overlap with compute or move "
                "to reduce-scatter/seq-parallel residual")
    if dom == "memory":
        return ("HBM traffic bound; fuse elementwise chains / cast "
                "accumulators bf16 / chunk the LM-head loss")
    return "near compute roofline; improve MXU utilization (layout/fusion)"


def dryrun_table(recs: List[Dict]) -> str:
    lines = ["| arch | shape | mesh | GiB/dev | HLO GFLOP/dev | coll GB/dev "
             "| collective mix |",
             "|---|---|---|---|---|---|---|"]
    for r in recs:
        mix = ", ".join(f"{k.replace('all-', 'a')}×{v}"
                        for k, v in sorted(
                            r.get("collective_counts", {}).items()))
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{_fmt_bytes(r['bytes_per_device'])} | "
            f"{r['hlo_flops_per_device'] / 1e9:.1f} | "
            f"{r['collective_bytes_per_device'] / 1e9:.2f} | {mix} |")
    return "\n".join(lines)


def roofline_table(recs: List[Dict]) -> str:
    lines = ["| arch | shape | compute s | memory s | collective s | "
             "dominant | useful-flops ratio | roofline frac | note |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in recs:
        if r["mesh"] != "16x16":
            continue
        uf = r.get("useful_flops_ratio")
        # ratio is meaningless for gather/segment workloads (HLO dot flops≈0)
        uf_s = f"{uf:.3f}" if uf is not None and uf < 50 else "n/a"
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | "
            f"{r['dominant']} | {uf_s} | "
            f"{r['roofline_fraction']:.3f} | {_note(r)} |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# --perf: measured kernel/planner performance -> PERF.md (DESIGN.md §11)
# ---------------------------------------------------------------------------

def _fmt_us(seconds: float) -> str:
    return f"{seconds * 1e6:.1f}"


def collect_perf(spec_name: str = "netflix-ci", repeats: int = 5) -> Dict:
    """Run the planned kernels on the named experiment spec with tracing on;
    returns ``{"plans": ..., "rooflines": ..., "machine": ...}``.

    Eager planned_* calls feed the predicted-vs-measured table (planner
    dispatch spans + §5.3 cost estimates); ``profile_jitted`` reports each
    kernel's achieved-vs-peak roofline fraction from the compiled HLO."""
    import jax

    from repro import obs, planner
    from repro.data import streaming
    from repro.data.pipeline import CompletionDataset
    from repro.kernels import ops as kops
    from repro.launch.experiment import SPECS

    spec = SPECS[spec_name]
    chunks = streaming.make_stream(spec.dataset, spec.seed, spec.shape,
                                   spec.nnz, spec.chunk_size,
                                   zipf_a=spec.zipf_a)
    ds = CompletionDataset.from_stream(chunks, spec.shape,
                                       num_shards=spec.num_shards,
                                       bucket_modes=(0,))
    st, omega = ds.tensor, ds.omega
    ks = jax.random.split(jax.random.PRNGKey(spec.seed), st.ndim + 1)
    factors = [jax.random.normal(k, (d, spec.rank)) / spec.rank ** 0.5
               for k, d in zip(ks, spec.shape)]
    x = jax.random.normal(ks[-1], (spec.shape[0], spec.rank))

    was_enabled = obs.enabled()
    if not was_enabled:
        obs.enable()
    try:
        # eager planned runs -> predicted-vs-measured plan table. One warmup
        # round pays per-plan tracing/compile, then the registry is reset so
        # the table reports steady-state eager dispatch only.
        for _ in range(2):
            planner.planned_mttkrp(st, [None] + factors[1:], mode=0)
            planner.planned_tttp(st, factors)
            planner.planned_cg_matvec(omega, factors, 0, x)
        obs.get_registry().reset()
        for _ in range(repeats):
            planner.planned_mttkrp(st, [None] + factors[1:], mode=0)
            planner.planned_tttp(st, factors)
            planner.planned_cg_matvec(omega, factors, 0, x)
        plans = obs.get_registry().summary()["plans"]

        # jitted roofline profiles: the same kernels the planner dispatches
        # to, compiled standalone so the HLO terms are attributable
        buckets = st.row_buckets(0, 64)
        rooflines = [
            obs.profile_jitted(
                lambda b, fs: kops.mttkrp_bucketed(
                    b, [None] + fs, num_rows=spec.shape[0]),
                buckets, factors[1:], name="mttkrp_bucketed"),
            obs.profile_jitted(
                lambda s, fs: kops.tttp_values(s, fs), st, factors,
                name="tttp"),
            obs.profile_jitted(
                lambda b, fs, x_: kops.cg_matvec_bucketed(
                    b, fs, x_, num_rows=spec.shape[0]),
                omega.row_buckets(0, 64), factors, x,
                name="cg_matvec_bucketed"),
        ]
    finally:
        if not was_enabled:
            obs.disable()
    return {"spec": spec_name, "plans": plans, "rooflines": rooflines,
            "machine": rooflines[0]["machine"]}


def plan_table(plans: Dict[str, Dict]) -> str:
    lines = ["| plan (expr \\| path \\| size) | kind | predicted s | "
             "measured mean s | measured min s | meas/pred |",
             "|---|---|---|---|---|---|"]
    for key in sorted(plans):
        p = plans[key]
        meas = p["measured"]
        lines.append(
            f"| `{key}` | {p['kind']} | {p['predicted']['seconds']:.2e} | "
            f"{meas['mean_s']:.2e} | {meas['min_s']:.2e} | "
            f"{p['measured_over_predicted']:.1f} |")
    return "\n".join(lines)


def kernel_roofline_table(rooflines: List[Dict]) -> str:
    lines = ["| kernel | measured µs | HLO GFLOP | HLO MiB | dominant | "
             "frac peak compute | frac peak memory | roofline frac |",
             "|---|---|---|---|---|---|---|---|"]
    for r in rooflines:
        lines.append(
            f"| {r['name']} | {_fmt_us(r['measured_s'])} | "
            f"{r['hlo_flops'] / 1e9:.4f} | {r['hlo_bytes'] / 2**20:.2f} | "
            f"{r['dominant']} | {r['frac_peak_compute']:.2e} | "
            f"{r['frac_peak_memory']:.2e} | {r['frac_roofline']:.2e} |")
    return "\n".join(lines)


def trajectory_tables(bench_dir: str) -> str:
    """One table per committed BENCH_*.json (the perf trajectory the
    regression gate compares fresh runs against)."""
    parts = []
    for path in sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json"))):
        group = os.path.basename(path)[len("BENCH_"):-len(".json")]
        with open(path) as f:
            entries = json.load(f)
        lines = [f"#### {group}", "", "| benchmark | µs/call |", "|---|---|"]
        for name in sorted(entries):
            v = entries[name]
            lines.append(f"| {name} | "
                         f"{'skipped' if v < 0 else f'{v:.1f}'} |")
        parts.append("\n".join(lines))
    return "\n\n".join(parts) if parts else "_no committed BENCH_*.json_"


def render_perf_md(perf: Dict, bench_dir: str) -> str:
    m = perf["machine"]
    return f"""# Performance report

Generated by `python -m repro.launch.report --perf` on the `{perf['spec']}`
spec. All numbers are host-dependent; the regression gate
(`benchmarks/compare.py`) compares like-for-like against the committed
baselines below rather than trusting absolute values.

Machine model (override via `REPRO_PEAK_FLOPS` / `REPRO_HBM_BW` /
`REPRO_LINK_BW`): peak {m['peak_flops']:.3g} FLOP/s, HBM
{m['hbm_bw']:.3g} B/s, link {m['link_bw']:.3g} B/s.

## Planner: predicted vs measured

The §5.3 cost model's per-plan prediction next to measured eager wall time
(best and mean over repeated runs; the first call includes compile). The constants matter only up to ranking — what this table
validates is that meas/pred is stable within a kernel family.

{plan_table(perf['plans'])}

## Kernels: achieved vs roofline

Compiled-HLO terms (dot FLOPs weighted by trip counts, HBM buffer traffic,
collective wire bytes — `repro.launch.roofline`) against the machine model.
`roofline frac` is best-case-bound-time / measured-time: 1.0 means running
at the machine-model bound. On CPU containers with TPU-default constants
these fractions are small; their trajectory over commits is the signal.

{kernel_roofline_table(perf['rooflines'])}

## Benchmark trajectory (committed baselines)

{trajectory_tables(bench_dir)}
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--section", default="both",
                    choices=["dryrun", "roofline", "both"])
    ap.add_argument("--perf", action="store_true",
                    help="measure kernels + planner on --spec and write "
                         "--out (default PERF.md)")
    ap.add_argument("--spec", default="netflix-ci",
                    help="experiment spec for --perf")
    ap.add_argument("--out", default="PERF.md",
                    help="output path for --perf")
    ap.add_argument("--bench-dir", default=".",
                    help="directory holding committed BENCH_*.json")
    ap.add_argument("--repeats", type=int, default=5,
                    help="eager planned runs per kernel for --perf")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.perf:
        perf = collect_perf(args.spec, repeats=args.repeats)
        text = render_perf_md(perf, args.bench_dir)
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.out}: {len(perf['plans'])} plan rows, "
              f"{len(perf['rooflines'])} kernel rooflines")
        return
    recs = load(args.dir)
    if args.section in ("dryrun", "both"):
        print("### Dry-run records (both meshes)\n")
        print(dryrun_table(recs))
        print()
    if args.section in ("roofline", "both"):
        print("### Roofline (single pod, 16×16 = 256 chips)\n")
        print(roofline_table(recs))


if __name__ == "__main__":
    main()
