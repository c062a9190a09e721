"""The cells of a benchmark at a tiny size, for the CPU tests: the same
harness, traffic and limits, with a small corpus.

Each configuration file may carry a ``"tiny"`` block, ``{group: {key:
value}}``, that replaces keys of its groups (``corpus``, ``serving``,
``prober``) at the tiny size; without one the corpus is cut to
``DEFAULT_TINY``. A new configuration, mix or metric file therefore runs
here with no edit to this module."""
import copy
import json
import pathlib
import time

from benchmarks.chip import harness, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_TINY = {"corpus": {"n": 16384}, "serving": {"capacity": 32768}}
TINY_RATE = 20.0        # reads per second at tiny size
TINY_SECONDS = 2


def bench(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def tiny(config: dict) -> dict:
    """The configuration cut to its tiny size. The query grid (targets
    1..max_card) stays the configuration's own, so the largest targets
    cover much of a tiny corpus."""
    c = copy.deepcopy(config)
    for group, kw in c.get("tiny", DEFAULT_TINY).items():
        c[group].update(kw)
    return c


def tiny_config(name: str, root: pathlib.Path = ROOT) -> dict:
    entry = {c["name"]: c for c in bench(root)["configs"]}[name]
    return tiny(json.loads((root / entry["file"]).read_text()))


# reads and a live write stream (the mix the write-path tests drive):
# zipf reads over 4,096 pairs, 32 new points 16 times a second
WRITES = {"reads": {"order": "zipf", "pool_pairs": 4096, "skew": 0.99},
          "ingest": {"batch_points": 32, "batches_per_s": 16, "noise": 0.05,
                     "warmup_batches": [32, 64, 128, 256]}}


def tiny_run(cell: str, seed: int = 2 ** 33 + 7, trace: bool = False,
             config: dict | None = None, rate: float = TINY_RATE,
             traffic: dict | None = None, drain_s: float = 60.0,
             log=lambda s: None, root: pathlib.Path = ROOT) -> dict:
    """One run of a cell of ``<root>/BENCHMARK.json`` at tiny size on the
    CPU, through harness.run as the CLI drives it (without the chip
    check). ``config`` and ``traffic`` replace the cell's own; the mix's
    read rate becomes ``rate`` (None keeps the mix's)."""
    b = bench(root)
    cfg, mix = run.load_cell(b, cell, root=root)
    traffic = copy.deepcopy(traffic or mix)
    if rate is not None:
        traffic["reads"]["rate_per_s"] = rate
    return harness.run(config or tiny(cfg), traffic, seed, TINY_SECONDS,
                       trace, e2e=harness.cell_metrics(b, cell, "end_to_end"),
                       per_layer=harness.cell_metrics(b, cell, "per_layer"),
                       t_start=time.perf_counter(), log=log, drain_s=drain_s,
                       base=root / run.CHIP)
