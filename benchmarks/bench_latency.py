"""Paper Table 4: online estimation latency (ms/query) per dataset × method.

Absolute numbers are CPU-host values (the paper used a 160-thread Xeon); the
claim validated is the RELATIVE ordering — PQ < exact for high-d, both
competitive with sampling.

``--batch-sweep`` (or :func:`run_batch_sweep`) measures the batched path
instead: queries/sec and per-query p50 latency of ``estimate_batch`` at
Q ∈ {1, 8, 64, 256}, validating that coalescing amortises the hash matmul
and candidate scan (DESIGN.md §9). The sweep runs TWO workload mixes —
``uniform`` (taus drawn uniformly from the dataset's radius grid, under
the §9 throughput-truncated ``serve_cfg``) and ``skew`` (a heavy-tailed
mix where ~1/8 of the requests carry a large tau and the rest a small
one, under the ε-faithful adaptive stopping config — see
:func:`adaptive_cfg`), the workload the compacting lane scheduler
(DESIGN.md §11) targets: per-lane stopping makes lane costs diverge, and
under the monolithic loop a batch pays for its slowest lane on every
lane. Output rows:
``{"dataset", "mix", "batch", "p50_ms_per_query", "qps",
"speedup_vs_base"}``; ``__main__`` snapshots them to ``BENCH_latency.json``
(benchmarks/README.md).

``--workload`` (or :func:`run_workload_sweep`) measures the estimate-cache
serving path (DESIGN.md §12) instead: each :mod:`benchmarks.workloads`
scenario (zipfian repeats, drifting popularity, correlated tau bands,
mixed ingest+query) is served twice through the SAME coalescer harness —
once with the cache (``cache_size > 0``), once without (the PR 4 prober
path) — with the two sides alternated round-robin so ambient load biases
them equally. Reported per (scenario, side): queries/sec (median across
rounds), hit/stale rates, evictions, and meanQ q-error where ground truth
is valid (every scenario except ``mixed``, whose ingests change it).
``--smoke`` shrinks the corpus and stream for the CI hot-path regression
gate. Workload rows carry a ``"workload"`` key and are MERGED into
``BENCH_latency.json`` alongside the batch-sweep rows.
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core import estimator as E
from repro.utils import compile_cache

BATCH_SIZES = (1, 8, 64, 256)
SKEW_HEAVY_FRAC = 0.125     # fraction of large-tau requests in the skew mix


def run(datasets=None):
    rows = []
    for name in datasets or common.DATASETS:
        ds = common.dataset(name)
        d = ds.x.shape[1]
        for meth, fn in {
            "DynamicProber": lambda: common.eval_prober(
                ds, common.prober_cfg(False, d)),
            "DynamicProber-PQ": lambda: common.eval_prober(
                ds, common.prober_cfg(True, d)),
            "Sampling1%": lambda: common.eval_sampling(ds, 0.01),
            "MLP-lite": lambda: common.eval_mlp(ds),
        }.items():
            out = fn()
            rows.append({"dataset": name, "method": meth,
                         "ms_per_query": out["ms_per_query"]})
            print(f"[latency] {name:9s} {meth:16s} "
                  f"{out['ms_per_query']:8.2f} ms/query")
    return rows


def adaptive_cfg(cfg):
    """ε-faithful stopping for the skewed sweep (DESIGN.md §11).

    ``serve_cfg`` truncates EVERY lane at ``max_visit/chunk = 4`` slabs —
    a throughput trade made for the monolithic scheduler (a batch pays for
    its slowest lane, so the old loop capped the slowest lane) that also
    flattens per-lane cost to ~4 slabs regardless of the workload,
    suppressing the very skew a skew sweep must measure. The skew mix
    therefore restores the paper's adaptive stopping (full default visit
    budget, ring budget covering the ~2a/ε samples a PTF decision needs,
    fine-grained chunks) on BOTH sides of any A/B: lane costs then span
    ~13-55 slabs and the scheduler — not the truncation — decides the
    wall-clock. All three fields predate the compacting scheduler, so the
    same config drives older checkouts unchanged.
    """
    return cfg.replace(chunk=128, ring_budget=2048, max_visit=8192)


def _sweep_requests(ds, pool: int, mix: str):
    """(qs, taus) for one workload mix. ``uniform`` draws taus uniformly
    from the per-query radius grid; ``skew`` gives a ``SKEW_HEAVY_FRAC``
    minority the LARGEST grid radius (slow lanes: high selectivity needs
    many Chernoff samples) and everyone else the smallest (fast lanes:
    PTF after a slab or two) — shuffled so every batch holds the mix."""
    rng = np.random.default_rng(0)
    queries = np.asarray(ds.queries)
    taus_all = np.asarray(ds.taus)
    qi = rng.integers(0, queries.shape[0], pool)
    if mix == "uniform":
        ti = rng.integers(0, taus_all.shape[1], pool)
        taus = taus_all[qi, ti]
    else:
        assert mix == "skew", mix
        heavy = rng.permutation(pool) < max(int(pool * SKEW_HEAVY_FRAC), 1)
        taus = np.where(heavy, taus_all[qi, -1], taus_all[qi, 0])
    return jnp.asarray(queries[qi]), jnp.asarray(taus.astype(np.float32))


def run_batch_sweep(batch_sizes=BATCH_SIZES, dataset: str = "sift",
                    pool: int = 256, reps: int = 5,
                    mixes=("uniform", "skew")):
    """Throughput/latency of ``estimate_batch`` vs batch size Q, per mix.

    A fixed pool of ``pool`` (query, tau) requests is processed at every
    batch size — Q=1 is the per-request dispatch baseline, larger Q
    coalesces the same workload into pool/Q jitted steps — using the
    throughput-tuned :func:`common.serve_cfg`. Measurement rounds are
    INTERLEAVED across batch sizes so ambient load on a shared/throttled
    host biases every Q equally. Reported per (mix, Q): p50 per-query
    latency (median per-batch wall time / Q) and queries/sec (Q / MEAN
    batch time — on the bimodal skew mix, small-Q batches are themselves
    bimodal, so a median would report the fast-lane rate rather than
    sustained throughput); ``speedup_vs_base`` is relative to that mix's
    Q=1.
    """
    assert pool >= max(batch_sizes), \
        f"pool={pool} must cover the largest batch size {max(batch_sizes)}"
    ds = common.dataset(dataset)
    base_cfg = common.serve_cfg(ds.x.shape[1])
    # build is stopping-config independent, so both mixes share the state
    st = E.build(ds.x, base_cfg, jax.random.PRNGKey(0))
    jax.block_until_ready(st.index.order)
    rows = []
    for mix in mixes:
        cfg = adaptive_cfg(base_cfg) if mix == "skew" else base_cfg
        qs, taus = _sweep_requests(ds, pool, mix)
        for q in batch_sizes:                            # compile per shape
            E.estimate_batch(st, qs[:q], taus[:q], cfg,
                             jax.random.PRNGKey(0)).block_until_ready()
        times: dict[int, list[float]] = {q: [] for q in batch_sizes}
        for r in range(reps):
            for q in batch_sizes:
                for b in range(max(pool // q, 1)):
                    lo = b * q
                    t0 = time.perf_counter()
                    E.estimate_batch(st, qs[lo:lo + q], taus[lo:lo + q], cfg,
                                     jax.random.PRNGKey(r * pool + b)
                                     ).block_until_ready()
                    times[q].append(time.perf_counter() - t0)
        base_q, base_qps = batch_sizes[0], None
        for q in batch_sizes:
            p50 = float(np.percentile(times[q], 50))
            qps = q / float(np.mean(times[q]))
            base_qps = qps if base_qps is None else base_qps
            rows.append({"dataset": dataset, "mix": mix, "batch": q,
                         "p50_ms_per_query": 1e3 * p50 / q, "qps": qps,
                         "speedup_vs_base": qps / base_qps})
            print(f"[latency-batch] {dataset:9s} {mix:8s} Q={q:4d} "
                  f"{1e3 * p50 / q:8.3f} ms/query p50  {qps:10.1f} q/s  "
                  f"({qps / base_qps:5.2f}x vs Q={base_q})")
    return rows


def _serve_workload(wl, co, batch: int):
    """Serve one workload stream in arrival order through ``co`` —
    flushing every ``batch`` queries — and time it end to end (lookups,
    miss probes, write-backs and ingest application all included). The
    fresh side is the SAME harness with ``cache_size=0``, so an A/B
    compares exactly the cache partition/merge step plus the probe work it
    saves. Returns ``(qps, served)`` with ``served`` the
    ``[(pool_idx, CardRequest), ...]`` stream in arrival order."""
    served, pending = [], []
    t0 = time.perf_counter()
    for kind, payload in wl.events:
        if kind == "ingest":
            co.ingest(payload)          # applied before the next flush
            continue
        q, tau, _ = wl.request(payload)
        pending.append((payload, co.submit(q, tau)))
        if len(pending) >= batch:
            co.flush()
            served.extend(pending)
            pending = []
    if pending:
        co.flush()
        served.extend(pending)
    dt = time.perf_counter() - t0
    return len(served) / dt, served


def run_workload_sweep(dataset: str = "sift", scenarios=None,
                       n_events: int = 1024, batch: int = 64,
                       pool: int = 64, skew: float = 0.99, reps: int = 3,
                       cache_size: int = 1024, reuse_tol: float = 0.0,
                       smoke: bool = False):
    """Cached-vs-fresh serving A/B across the workload scenarios (module
    docstring). The acceptance gate this sweep measures: on ``zipf``
    (skew ~0.99, Q=``batch``) the cached side sustains >= 2x queries/sec
    at ``reuse_tol=0`` with meanQ unchanged (exact-repeat hits are
    bit-identical, so any meanQ delta is sampling noise between sides'
    PRNG keys, not cache error).

    Each side keeps ONE coalescer across rounds and runs the stream once
    UNTIMED first (compiles every flush shape and brings the cache to
    steady state — serving is a long-running process; cold-start compiles
    and compulsory misses are setup cost, not throughput), then ``reps``
    timed rounds with the side order alternated round-robin so ambient
    load on a throttled host biases both sides equally. Hit/stale/evict
    rates are computed over the timed rounds only."""
    from benchmarks import workloads
    from repro.core import updates as U
    from repro.data import vectors
    from repro.serve.engine import CardinalityCoalescer

    scenarios = tuple(scenarios or workloads.SCENARIOS)
    if smoke:
        n_events, pool, batch, reps = 128, 32, 16, 1
        cache_size = 256
        ds = vectors.load(dataset, n_queries=6, scale=0.05)
    else:
        ds = common.dataset(dataset)
    cfg = common.serve_cfg(ds.x.shape[1])
    key = jax.random.PRNGKey(0)
    n = ds.x.shape[0]
    rows = []
    for sc in scenarios:
        # per-scenario sizing: drift's popularity universe must EXCEED the
        # cache so the sliding window actually exercises CLOCK eviction;
        # tau-corr additionally runs a reuse_tol>0 side (the banding knob
        # is what that scenario exists to measure)
        sc_pool, sc_cache = pool, cache_size
        if sc == "drift":
            sc_pool, sc_cache = pool * 4, max(pool // 2, 16)
        wl = workloads.generate(ds, sc, n_events=n_events, pool=sc_pool,
                                skew=skew, seed=0,
                                ingest_every=32 if smoke else 128)
        sides = {"fresh": (0, 0.0), "cached": (sc_cache, reuse_tol)}
        if sc == "tau-corr":
            sides["cached-tol"] = (sc_cache, max(reuse_tol, 0.25))
        # mixed re-applies its ingest events on EVERY pass (warm + timed
        # rounds) — size the spare capacity for all of them (DESIGN.md §10)
        n_ingest = sum(e[1].shape[0] for e in wl.events if e[0] == "ingest")
        capacity = U.next_capacity(n, n + (reps + 1) * n_ingest) \
            if n_ingest else None
        state = E.build(ds.x, cfg, key, track_epochs=True,
                        capacity=capacity)
        jax.block_until_ready(state.index.order)
        cos = {side: CardinalityCoalescer(state, cfg, key, max_batch=batch,
                                          cache_size=cs, reuse_tol=tol)
               for side, (cs, tol) in sides.items()}
        for side in cos:                       # untimed warm pass
            _serve_workload(wl, cos[side], batch)
        stats0 = {side: dict(cos[side].cache_stats) for side in cos}
        qps: dict[str, list[float]] = {side: [] for side in cos}
        last = {}
        for r in range(reps):
            # alternate side order round-robin (throttled-host fairness)
            order = list(cos) if r % 2 == 0 else list(cos)[::-1]
            for side in order:
                q, served = _serve_workload(wl, cos[side], batch)
                qps[side].append(q)
                last[side] = served
        for side in cos:
            served = last[side]
            stats = {k: cos[side].cache_stats[k] - stats0[side][k]
                     for k in stats0[side]}
            qerrs = [common.qerror(req.est, wl.truth[pi])
                     for pi, req in served] if sc != "mixed" else None
            looked = max(stats["lookups"], 1)
            row = {"dataset": dataset, "workload": sc, "batch": batch,
                   "side": side, "reuse_tol": sides[side][1],
                   "n_events": len(served),
                   "qps": float(np.median(qps[side])),
                   "qps_rounds": [round(v, 1) for v in qps[side]],
                   "hit_rate": stats["hits"] / looked,
                   "stale_rate": stats["stale"] / looked,
                   "evicts": stats["evicts"],
                   "mean_qerror": float(np.mean(qerrs)) if qerrs else None}
            if side != "fresh":
                pairs = [c / f for c, f in zip(qps[side], qps["fresh"])]
                row["speedup_vs_fresh"] = float(np.median(pairs))
                row["speedup_rounds"] = [round(v, 2) for v in pairs]
            rows.append(row)
            print(f"[workload] {dataset:9s} {sc:8s} {side:10s} "
                  f"{row['qps']:9.1f} q/s  hit={row['hit_rate']:.2f} "
                  f"stale={row['stale_rate']:.2f} "
                  f"meanQ={row['mean_qerror'] if qerrs else float('nan'):.3f}"
                  + (f"  ({row['speedup_vs_fresh']:.2f}x vs fresh)"
                     if side != "fresh" else ""))
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    # distinct tags per sweep — the batch/skew rows are the longitudinal
    # scheduling record and must not be clobbered by a methods-only run;
    # workload rows share the latency tag but merge (carry a "workload"
    # key) instead of clobbering the batch rows, and vice versa
    args = sys.argv[1:]
    if "--workload" in args:
        rows = run_workload_sweep(smoke="--smoke" in args)
        if "--smoke" in args:       # CI gate: never clobber the committed
            pass                    # record with tiny-corpus numbers
        else:
            common.write_bench_json("latency", rows,
                                    meta={"sweep": ["workload"]},
                                    retain=lambda r: "workload" not in r)
    elif "--batch-sweep" in args:
        rows = run_batch_sweep()
        common.write_bench_json("latency", rows, meta={"sweep": ["batch"]},
                                retain=lambda r: "workload" in r)
    else:
        rows = run()
        common.write_bench_json("methods", rows, meta={"sweep": ["latency"]})
