"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable progress
lines prefixed with [tag]) and snapshots the latency / q-error sections to
machine-readable JSON at the repo root — the perf trajectory diffed across
PRs (benchmarks/README.md). The committed record is three files:
``BENCH_latency.json`` (the batch/skew scheduling sweep + the workload
cache sweep), ``BENCH_methods.json`` (per-method latency) and
``BENCH_qerror.json`` (accuracy). A selected section that fails to produce
its documented snapshot is a hard error — the committed record must never
silently go missing.

  PYTHONPATH=src python -m benchmarks.run             # everything
  PYTHONPATH=src python -m benchmarks.run qerror adc  # a subset
"""
from __future__ import annotations

import sys

from repro.utils import compile_cache

# section name -> the BENCH_<tag>.json snapshot it is documented to write
SNAPSHOT_TAGS = {"latency": "methods", "batch": "latency",
                 "workload": "latency", "qerror": "qerror"}


def main() -> None:
    compile_cache.enable()
    which = set(sys.argv[1:]) or {"qerror", "latency", "batch", "workload",
                                  "build", "adc", "epsilon", "updates",
                                  "roofline"}
    csv: list[tuple[str, float, str]] = []
    method_rows: list[dict] = []
    batch_rows: list[dict] = []
    workload_rows: list[dict] = []
    qerror_rows: list[dict] = []

    if "qerror" in which:
        from benchmarks import bench_qerror
        for r in bench_qerror.run():
            qerror_rows.append(r)
            csv.append((f"qerror/{r['dataset']}/{r['method']}", 0.0,
                        f"meanQ={r['mean']:.3f};p90={r['p90']:.3f};"
                        f"p99={r['p99']:.3f};max={r['max']:.3f}"))
    if "latency" in which:
        from benchmarks import bench_latency
        for r in bench_latency.run():
            method_rows.append(r)
            csv.append((f"latency/{r['dataset']}/{r['method']}",
                        1e3 * r["ms_per_query"], "online-estimate"))
    if "batch" in which:
        from benchmarks import bench_latency
        for r in bench_latency.run_batch_sweep():
            batch_rows.append(r)
            csv.append((f"latency-batch/{r['dataset']}/"
                        f"{r.get('mix', 'uniform')}/Q{r['batch']}",
                        1e3 * r["p50_ms_per_query"],
                        f"qps={r['qps']:.0f};"
                        f"speedup={r['speedup_vs_base']:.2f}x"))
    if "workload" in which:
        from benchmarks import bench_latency
        for r in bench_latency.run_workload_sweep():
            workload_rows.append(r)
            extra = f";speedup={r['speedup_vs_fresh']:.2f}x" \
                if "speedup_vs_fresh" in r else ""
            csv.append((f"workload/{r['dataset']}/{r['workload']}/"
                        f"{r['side']}", 0.0,
                        f"qps={r['qps']:.0f};hit={r['hit_rate']:.2f}"
                        + extra))
    if "build" in which:
        from benchmarks import bench_build
        for r in bench_build.run():
            csv.append((f"build/{r['dataset']}", 0.0,
                        f"lsh={r['lsh_s']:.2f}s;table={r['table_s']:.2f}s;"
                        f"pq={r['pq_s']:.2f}s;mlp={r['mlp_train_s']:.2f}s"))
    if "adc" in which:
        from benchmarks import bench_adc
        for r in bench_adc.run():
            csv.append((f"adc/d{r['dim']}", 1e3 * r["t_adc_ms"],
                        f"speedup={r['speedup']:.2f}x"))
    if "epsilon" in which:
        from benchmarks import bench_epsilon
        for r in bench_epsilon.run():
            csv.append((f"epsilon/{r['eps']}", 1e3 * r["ms_per_query"],
                        f"meanQ={r['mean_qerror']:.3f}"))
    if "updates" in which:
        from benchmarks import bench_updates
        for r in bench_updates.run():
            csv.append((f"updates/{r['dataset']}", 1e6 * r["t_update_s"],
                        f"updatedQ={r['qerr_updated_mean']:.2f};"
                        f"staticQ={r['qerr_static_mean']:.2f};"
                        f"mlpFrozenQ={r['qerr_mlp_frozen_mean']:.2f};"
                        f"rebuild_s={r['t_rebuild_s']:.2f}"))
    if "roofline" in which:
        from pathlib import Path

        from benchmarks import bench_roofline
        variants = [("baseline", "results/dryrun")]
        if Path("results/dryrun_opt").exists():
            variants.append(("optimized", "results/dryrun_opt"))
        for tag, d in variants:
            for mesh in ("single", "multi"):
                for r in bench_roofline.run(d, mesh=mesh):
                    name = f"roofline-{tag}/{r['arch']}/{r['shape']}/{mesh}"
                    if "skipped" in r:
                        csv.append((name, 0.0, "skipped"))
                    else:
                        csv.append((name,
                                    1e6 * max(r["t_compute"], r["t_memory"],
                                              r["t_collective"]),
                                    f"dominant={r['dominant']};"
                                    f"useful={r['useful_ratio']:.2f};"
                                    f"mfu_bound={r['mfu_bound']:.3f};"
                                    f"peak_gib={r['peak_gib']:.2f}"))

    # distinct tags per sweep so a subset run never clobbers another sweep's
    # committed record: BENCH_latency.json = the batch/skew scheduling sweep
    # + the workload cache sweep (merged rows; workload rows carry a
    # "workload" key), BENCH_methods.json = per-method latency,
    # BENCH_qerror.json = accuracy
    from benchmarks import common
    written: set[str] = set()
    if method_rows:
        common.write_bench_json("methods", method_rows,
                                meta={"sweep": ["latency"]})
        written.add("methods")
    latency_meta = {"sweep": [s for s, rs in
                              (("batch", batch_rows),
                               ("workload", workload_rows)) if rs]}
    if batch_rows and workload_rows:
        common.write_bench_json("latency", batch_rows + workload_rows,
                                meta=latency_meta)
    elif batch_rows:
        common.write_bench_json("latency", batch_rows, meta=latency_meta,
                                retain=lambda r: "workload" in r)
    elif workload_rows:
        common.write_bench_json("latency", workload_rows, meta=latency_meta,
                                retain=lambda r: "workload" not in r)
    if batch_rows or workload_rows:
        written.add("latency")
    if qerror_rows:
        common.write_bench_json("qerror", qerror_rows)
        written.add("qerror")

    # fail LOUDLY if a selected section did not produce its documented
    # snapshot — a silently missing BENCH_*.json breaks the cross-PR
    # trajectory record this driver exists to maintain
    missing = {f"{sec} -> BENCH_{tag}.json"
               for sec, tag in SNAPSHOT_TAGS.items()
               if sec in which and tag not in written}
    if missing:
        raise SystemExit("documented benchmark snapshots were not written: "
                         + ", ".join(sorted(missing)))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
