"""Chip benchmark of the cardinality-estimation service.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in the checkout's ``BENCHMARK.json``;
it names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``). The run builds the configuration's corpus and
index (from its ``data_seed``), draws the order of the mix's reads from
``--seed``, warms every shape the mix uses, serves the mix open loop for
``--seconds`` and checks every answer against the exact count
(harness.py). Set-up phases, the window's counts and each number compared
with its limit go to standard error; the last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device`` and, traced, ``breakdown``; ``checks`` comes last.

Exits 2 with no result when the first device is not a TPU or there are
fewer devices than the cell asks for. Two options are for measuring the
benchmark itself, and its own runs never use them: ``--control adc``
runs the lower-precision control (every distance from the 8-bit PQ codes,
the program's ADC path, where the configuration computes the central
bucket and near rings in float32) in place of the configuration's path,
and ``--rate``
replaces the mix's read rate (the knee sweep, sweep.py).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
CHIP = pathlib.Path("benchmarks/chip")        # the benchmark, under ROOT
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# the lower-precision control: the program's own code path, distances from
# the 8-bit PQ codes (ADC) where the configuration computes them in float32
CONTROLS = {"adc": {"pq_exact_central": False, "pq_exact_rings": 0}}


def load_cell(bench: dict, workload: str, control: str | None = None,
              rate: float | None = None,
              root: pathlib.Path = ROOT) -> tuple[dict, dict]:
    """The configuration and traffic mix of a cell, found by name under
    ``root``, with the control's and the rate's replacements applied."""
    from benchmarks.chip import harness
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    if control:
        config["prober"].update(CONTROLS[control])
    traffic = harness.load_json("traffic", cell["traffic"], root / CHIP)
    if rate is not None:
        traffic["reads"]["rate_per_s"] = rate
    return config, traffic


def main(argv=None, t_start: float = T_START) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=sorted(CONTROLS))
    ap.add_argument("--rate", type=float)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"needs {cell['chips']} TPU chip(s); JAX finds "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 2

    from repro.utils import compile_cache
    from benchmarks.chip import harness
    print(f"[setup] compile_cache={compile_cache.enable()}", file=sys.stderr)
    # every program goes to the cache, however quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    config, traffic = load_cell(bench, args.workload, args.control,
                                args.rate)
    out = harness.run(
        config, traffic, args.seed, args.seconds, bool(args.trace),
        e2e=harness.cell_metrics(bench, args.workload, "end_to_end"),
        per_layer=harness.cell_metrics(bench, args.workload, "per_layer"),
        t_start=t_start)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
