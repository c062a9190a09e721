"""The one traffic generator. A traffic mix is a JSON file of parameters,
``traffic/<mix>.json``; this module turns it and a seed into a schedule.

Reads (``"reads"``) arrive at ``rate_per_s`` over the window:
``round(rate * seconds)`` requests, whose gaps are the same set of
exponential quantiles for every seed, put in an order drawn from the seed,
so every seed offers the same amount of work with Poisson-like bursts.
Two optional keys change how they arrive:

* ``"burst": {"on_s": a, "off_s": b}`` — on/off arrivals: the same reads
  fall only in the on-periods (``a`` seconds on, ``b`` off, from 0), so
  the rate while on is ``rate * (a + b) / a``.
* ``"outstanding": k`` — closed loop: at most ``k`` reads are in flight,
  and the next is due when one is answered; ``round(rate * seconds)`` is
  then only the most the window can draw.

Each read names one (query, tau) pair of the pool, a grid of pool queries
(corpus points) times the configuration's target cardinalities:

* ``"order": "distinct"`` — every read is a different pair: the first
  ``R`` pairs of the pool, query by query, in an order drawn from the seed,
  so every seed reads the same pairs; the pool has just enough queries
  for the window.
* ``"order": "zipf"`` — ``pool_pairs`` pairs are sampled from the grid
  and rank-shuffled, and reads repeat them with zipf ``skew``
  (copied from ``benchmarks/workloads.py``: ``_zipf_probs``,
  ``_request_pool``).

Writes (``"ingest"``, optional) arrive as ``batch_points`` new points
every ``1 / batches_per_s`` seconds; each point is a random live corpus
row plus ``noise`` times a standard normal (in-distribution growth,
copied from ``workloads._ingest_batch``). ``warmup_batches`` more batches,
of the listed sizes, are drawn for set-up.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    read_t: np.ndarray        # (R,) due time of each read, seconds
                              # (0 for every read of a closed loop)
    outstanding: int | None   # closed loop: reads in flight at most
    read_pair: np.ndarray     # (R,) pool pair of each read
    pool_queries: int         # query points in the pool
    pair_query: np.ndarray    # (P,) pool query of each pair
    pair_target: np.ndarray   # (P,) target-grid index of each pair
    ingest_t: np.ndarray      # (G,) due time of each write batch
    ingest_rows: np.ndarray   # (G, b) corpus rows the new points lie near
    ingest_noise: np.ndarray  # (G, b, d) offsets from those rows
    warm_rows: tuple          # set-up write batches: rows, one per size
    warm_noise: tuple         # ... and offsets


def _zipf_probs(pool: int, skew: float) -> np.ndarray:
    p = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** skew
    return p / p.sum()


def arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times of ``round(rate * seconds)`` open-loop arrivals in
    [0, seconds): a seed-independent set of exponential gaps in a
    seed-drawn order, scaled to fill the window."""
    n = int(round(rate * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def on_off(t: np.ndarray, on_s: float, off_s: float) -> np.ndarray:
    """Times ``t`` on a clock that runs only in the on-periods, mapped to
    the wall clock (``on_s`` on, ``off_s`` off, starting on)."""
    return np.floor(t / on_s) * (on_s + off_s) + np.mod(t, on_s)


def make_schedule(traffic: dict, seed: int, seconds: float, n_corpus: int,
                  dim: int, n_targets: int) -> Schedule:
    rng = np.random.default_rng(seed)
    reads = traffic["reads"]
    read_t = arrivals(float(reads["rate_per_s"]), seconds, rng)
    burst = reads.get("burst")
    if burst:
        on, off = float(burst["on_s"]), float(burst["off_s"])
        read_t = on_off(read_t * on / (on + off), on, off)
    outstanding = reads.get("outstanding")
    if outstanding is not None:
        outstanding = int(outstanding)
        read_t = np.zeros_like(read_t)
    n_reads = len(read_t)
    if reads["order"] == "distinct":
        pool_q = max(1, math.ceil(n_reads / n_targets))
        pairs = rng.permutation(n_reads)
        read_pair = np.arange(n_reads)
    elif reads["order"] == "zipf":
        pool = int(reads["pool_pairs"])
        pool_q = math.ceil(pool / n_targets)
        pairs = rng.permutation(pool_q * n_targets)[:pool]
        read_pair = rng.choice(pool, size=n_reads,
                               p=_zipf_probs(pool, float(reads["skew"])))
    else:
        raise ValueError(f"unknown read order {reads['order']!r}")
    pair_query, pair_target = np.divmod(pairs, n_targets)

    ing = traffic.get("ingest")
    if ing:
        b = int(ing["batch_points"])
        ingest_t = np.arange(0.0, seconds, 1.0 / float(ing["batches_per_s"]))
        noise = float(ing["noise"])
        ingest_rows = rng.integers(0, n_corpus, (len(ingest_t), b))
        ingest_noise = (noise * rng.standard_normal(
            (len(ingest_t), b, dim))).astype(np.float32)
        sizes = [int(s) for s in ing.get("warmup_batches", [])]
        warm_rows = tuple(rng.integers(0, n_corpus, s) for s in sizes)
        warm_noise = tuple((noise * rng.standard_normal((s, dim))
                            ).astype(np.float32) for s in sizes)
    else:
        ingest_t = np.zeros(0)
        ingest_rows = np.zeros((0, 0), np.int64)
        ingest_noise = np.zeros((0, 0, dim), np.float32)
        warm_rows = warm_noise = ()
    return Schedule(read_t, outstanding, read_pair, pool_q, pair_query,
                    pair_target, ingest_t, ingest_rows, ingest_noise,
                    warm_rows, warm_noise)
