"""Profile one cell with the program's own spans, counters and scopes.

    python3 benchmarks/chip/profile_cell.py --workload <cell> --seed <n> \
        --seconds <s> [--out <dir>] [--sample <dir>]

Serves the cell's window as run.py does (``harness.Cell``,
``harness.serve_window``) and profiles its last ``trace.TRACE_SECONDS``,
taking the coalescer's ``stats`` counters when the profile starts and
stops. After the window it compiles the probe step at each served flush
size for its HLO text, reduces the profile with ``phases.summarize``,
checks every answer as run.py does, and prints one JSON line:
``correct``; ``metrics``, the cell's end-to-end and per-layer metrics and
those of ``PHASE_METRICS``, each from its reader in ``metrics/``;
``counters``; ``breakdown`` (device ops named by scope, idle gaps named by
the innermost ``bench/*`` or ``coal/*`` span, and per flush the launches
and idle seconds by ``coal/*`` phase); ``scopes`` (device ms per
``estimate_batch_stats`` call by scope, and its largest unscoped ops);
``device``; ``checks``.

``--out`` keeps the profile, the HLO text (both gzipped) and the line.
``--sample`` also records, after the window, a small profile of three
flushes (8, 4 and 2 fresh reads) with the HLO text its reduction needs,
as ``testdata/`` keeps them. Exits 2 with no result when the first device
is not a TPU, as run.py does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from benchmarks.chip import harness, phases, run  # noqa: E402
from repro.core.updates import next_pow2  # noqa: E402

PHASE_METRICS = {"engine.programs_per_flush": "programs",
                 "engine.syncs_per_flush": "reads",
                 "engine.pad_lane_pct": "%",
                 "engine.lookup_ms": "ms",
                 "prober.rings_device_ms": "ms",
                 "prober.central_device_ms": "ms",
                 "prober.slab_device_ms": "ms"}
PROBE = r"estimate_batch_stats"
SAMPLE = "v5e_coal_flushes"
SAMPLE_FLUSHES = (8, 4, 2)


def _gzip(src, dst: pathlib.Path):
    with open(src, "rb") as f, gzip.open(dst, "wb") as g:
        shutil.copyfileobj(f, g)


def record_sample(cell, out: pathlib.Path, flushes=SAMPLE_FLUSHES):
    """A profile of a few flushes of fresh reads (pool queries at radii off
    the grid, so the cache misses), marked as the harness marks a window,
    with the HLO text of their probe shapes: ``<out>/SAMPLE.*``."""
    coal = cell.coal
    cap = phases.capture(coal.stats)
    cap.start()
    i = 0
    with harness._span("bench/window"):
        for size in flushes:
            with harness._span("bench/flush"):
                with harness._span("bench/submit"):
                    for _ in range(size):
                        q, t = divmod(i, cell.pool_tau.shape[1])
                        coal.submit(cell.pool_q[q],
                                    cell.pool_tau[q, t] * 1.001)
                        i += 1
                coal.flush()
    cap.stop()
    out.mkdir(parents=True, exist_ok=True)
    _gzip(cap.path, out / f"{SAMPLE}.xplane.pb.gz")
    cap.cleanup()
    sizes = sorted({next_pow2(s) for s in flushes})
    with gzip.open(out / f"{SAMPLE}.hlo.txt.gz", "wt") as f:
        f.write("\n".join(phases.served_hlo(coal, sizes)))
    (out / f"{SAMPLE}.counters.json").write_text(json.dumps(cap.counters))


def profile(config: dict, traffic: dict, seed: int, seconds: float,
            metrics: list, t_start: float, log, out=None, sample=None,
            drain_s: float = harness.DRAIN_S,
            base: pathlib.Path = harness.CHIP_DIR) -> dict:
    """One profiled run of a cell (module docstring); ``metrics`` are the
    cell's entries of ``BENCHMARK.json`` to read besides PHASE_METRICS."""
    cell = harness.Cell(config, traffic, seed, seconds, log)
    dev = jax.devices()[0]
    gc.collect()
    gc.freeze()
    cap = phases.capture(cell.coal.stats)
    setup_s = time.perf_counter() - t_start
    rec = harness.serve_window(cell, seconds, drain_s, tracer=cap)
    gc.unfreeze()
    sizes = [1 << k for k in range(cell.max_batch.bit_length())]
    hlo = phases.served_hlo(cell.coal, sizes)
    if sample is not None:
        record_sample(cell, pathlib.Path(sample))
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    cache_stats = dict(cell.coal.cache_stats)
    n_valid = int(cell.coal.state.n_valid)
    cell.coal = None                   # free the program's state first
    gc.collect()
    truth, numbers = harness.check(cell, rec, n_valid)

    if out is not None:
        out = pathlib.Path(out)
        out.mkdir(parents=True, exist_ok=True)
        _gzip(cap.path, out / "window.xplane.pb.gz")
        with gzip.open(out / "window.hlo.txt.gz", "wt") as f:
            f.write("\n".join(hlo))
        (out / "counters.json").write_text(json.dumps(cap.counters))
    summary = phases.summarize(cap.path, hlo)
    cap.cleanup()
    run = harness.Run(seconds=seconds, setup_s=setup_s, due=rec["due"],
                      done=rec["done"], est=rec["est"], prov=rec["prov"],
                      nvisited=rec["nvisited"], truth=truth,
                      cache_stats=cache_stats, gave_up=rec["gave_up"],
                      trace=summary)
    run.counters = cap.counters
    units = {m["name"]: m["unit"] for m in metrics}
    units.update(PHASE_METRICS)
    values = {}
    for name, unit in units.items():
        v = harness.load_metric(name, base).read(run)
        if v is not None:
            values[name] = {"value": float(v), "unit": unit}
    correct, shown = harness.verdict(numbers, config["correct"]["limits"])
    result = {
        "correct": correct, "metrics": values, "counters": cap.counters,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "memory_peak_bytes": mem, "busy_s": summary.busy_s,
                   "window_s": summary.window_s},
        "breakdown": summary.breakdown(),
        "scopes": {"ms_per_call": summary.scope_ms(PROBE),
                   "unscoped_ops_ms": summary.unscoped_ops(PROBE)},
        "checks": shown}
    if out is not None:
        (out / "result.json").write_text(json.dumps(result, indent=1))
    return result


def main(argv=None, t_start: float = T_START) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--out")
    ap.add_argument("--sample")
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cells[args.workload]["chips"]:
        print(f"needs {cells[args.workload]['chips']} TPU chip(s); JAX finds "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 2
    from repro.utils import compile_cache
    print(f"[setup] compile_cache={compile_cache.enable()}", file=sys.stderr)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    config, traffic = run.load_cell(bench, args.workload)
    metrics = harness.cell_metrics(bench, args.workload, "end_to_end") + \
        harness.cell_metrics(bench, args.workload, "per_layer")
    out = profile(config, traffic, args.seed, args.seconds, metrics,
                  t_start, lambda s: print(s, file=sys.stderr, flush=True),
                  out=args.out, sample=args.sample)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
