"""One run of one cell: set-up, the open-loop window, the check.

The system under test is ``repro.serve.engine.CardinalityCoalescer``
(``submit`` / ``ingest`` / ``flush``, estimate cache on) over a state from
``repro.core.estimator.build(..., capacity=)``. Everything else here is the
benchmark's own: the corpus and traffic generators, the exact reference,
the metric readers (``metrics/<name>.py``) and the trace reduction.

Serving loop, one process and one thread (``serve_window``):

1. hand every due write batch to ``coal.ingest``;
2. submit every read that is due, each stamped with its due time (the
   coalescer runs a batch whenever ``max_batch`` reads are pending);
3. if anything is pending, ``coal.flush()`` and read the answers;
   otherwise sleep to the next due time.

After the window, the reads due in it that are still waiting are served
(no new arrivals), the peak device memory is read, the program's state is
dropped, and every answer is compared with the exact count of the points
that were visible to it.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator as E
from repro.core.config import ProberConfig
from repro.serve.engine import CardinalityCoalescer

from benchmarks.chip import corpus, reference, stats, traffic as traffic_mod
from benchmarks.chip import trace as trace_mod

CHIP_DIR = pathlib.Path(__file__).resolve().parent
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
DRAIN_S = 60.0          # how long past the close a due read may wait


# ------------------------------------------------------------ lookup ----
def load_json(kind: str, name: str, base: pathlib.Path = CHIP_DIR) -> dict:
    """``<base>/<kind>/<name>.json`` — a configuration or a traffic mix."""
    return json.loads((base / kind / f"{name}.json").read_text())


def load_metric(name: str, base: pathlib.Path = CHIP_DIR):
    """The reader module ``<base>/metrics/<name>.py``; its ``read(run)``
    returns the metric's value, or None when there is nothing to read."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The entries of ``bench[kind]`` that this cell reports: those that
    list it under ``workloads``, or that have no such list and move (or,
    end to end, are) a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench[kind]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def prober_config(config: dict) -> ProberConfig:
    return ProberConfig(**config["prober"])


# ------------------------------------------------------------ clocks ----
class CompileClock:
    """Wall and backend-compile seconds of a phase (the compile listener
    of ``chip_smoke.py``)."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0

    def _on(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.compile_s += duration
            self.compiles += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self.t0
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcPauses:
    """Collections of the Python collector while open: per generation the
    count, and the longest and total pause in ms."""

    def __init__(self):
        self.by_gen: dict = {}

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            ms = 1e3 * (time.perf_counter() - self._t)
            n, mx, tot = self.by_gen.get(info["generation"], (0, 0.0, 0.0))
            self.by_gen[info["generation"]] = (n + 1, max(mx, ms), tot + ms)

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def summary(self) -> dict:
        return {f"gen{g}": {"count": n, "max_ms": mx, "total_ms": tot}
                for g, (n, mx, tot) in sorted(self.by_gen.items())}


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer seed (64 bits and up)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


# ------------------------------------------------------------ record ----
@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers take their numbers here."""
    seconds: float
    setup_s: float
    due: np.ndarray             # (R,) due time of every read in the window
    done: np.ndarray            # (R,) answer time, nan if never answered
    est: np.ndarray             # (R,) estimate
    prov: list                  # (R,) "probe" | "hit" | "stale-refresh"
    nvisited: list              # samples drawn by each probed read
    truth: np.ndarray | None = None        # (R,) exact count
    gave_up: float = math.inf              # when the loop stopped waiting
    cache_stats: dict = dataclasses.field(default_factory=dict)
    trace: "trace_mod.TraceSummary | None" = None

    @property
    def answered(self) -> np.ndarray:
        return ~np.isnan(self.done)


# ------------------------------------------------------------ set-up ----
class Cell:
    """Data, index and a warm coalescer for one configuration and mix."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 seconds: float, log):
        self.config, self.traffic, self.log = config, traffic, log
        self.cfg = prober_config(config)
        c, s = config["corpus"], config["serving"]
        self.n, self.d = int(c["n"]), int(c["d"])
        self.max_batch = int(s["max_batch"])
        # the dataset (corpus, query pool, index) is the configuration's,
        # drawn from its ``data_seed``; the run's seed draws the order of
        # the reads and their gaps, the serving key and the warm-up reads,
        # so every seed serves the same work in another order
        k_shape, k_x, k_q, k_build = jax.random.split(
            jax.random.PRNGKey(int(config["corpus"]["data_seed"])), 4)
        k_serve, k_warm = jax.random.split(seed_key(seed))
        self.targets = corpus.targets(int(config["queries"]["max_card"]),
                                      int(config["queries"]["n_taus"]))

        self._k_data = k_shape, k_x
        with CompileClock() as clk:
            self.x = self.corpus()
            self.sched = traffic_mod.make_schedule(
                traffic, seed, seconds, self.n, self.d, len(self.targets))
            _, qs, taus = corpus.query_grid(k_q, self.x,
                                            self.sched.pool_queries,
                                            self.targets)
            self.pool_q = np.asarray(qs)
            self.pool_tau = np.asarray(taus)
            sch = self.sched
            self.ingest_pts = self._points(sch.ingest_rows, sch.ingest_noise)
            self.warm_pts = [self._points(r[None], z[None])[0]
                             for r, z in zip(sch.warm_rows, sch.warm_noise)]
        self.phase("data", clk, reads=len(sch.read_t),
                   pool_queries=sch.pool_queries,
                   write_batches=len(sch.ingest_t))

        cap = int(s["capacity"])
        total = self.n + sum(len(p) for p in self.warm_pts) + \
            self.ingest_pts.shape[0] * self.ingest_pts.shape[1]
        if total > cap:
            raise ValueError(f"the window would grow the corpus to {total} "
                             f"rows, past the capacity {cap}")
        with CompileClock() as clk:
            state = E.build(self.x, self.cfg, k_build, capacity=cap)
            jax.block_until_ready(state)
        self.phase("build", clk, n_valid=int(state.n_valid), capacity=cap)

        self.coal = CardinalityCoalescer(
            state, self.cfg, k_serve, max_batch=self.max_batch,
            cache_size=int(s["cache_size"]),
            reuse_tol=float(s["reuse_tol"]))
        self.handed = 0          # points handed to coal.ingest so far
        with CompileClock() as clk:
            self._warm_up(k_warm)
        self.phase("warm_up", clk, points=self.handed)
        self.x = None        # the index holds its own copy

    def corpus(self) -> jax.Array:
        """The configuration's corpus, (n, d) float32 on the device, from
        its ``data_seed``. Made again for the reference rather than kept
        beside the index."""
        c = self.config["corpus"]
        return corpus.make_corpus(
            *self._k_data, self.n, self.d, n_clusters=int(c["n_clusters"]),
            intrinsic_dim=int(c["intrinsic_dim"]), noise=float(c["noise"]))

    def phase(self, name: str, clk: CompileClock, **kw):
        self.log(f"[setup] {name} wall_s={clk.wall_s:.3f} "
                 f"compile_s={clk.compile_s:.3f} compiles={clk.compiles} "
                 + " ".join(f"{k}={v}" for k, v in kw.items()))

    def _points(self, rows: np.ndarray, noise: np.ndarray) -> np.ndarray:
        """New points: corpus rows plus offsets, (G, b, d) float32."""
        if rows.size == 0:
            return np.zeros(rows.shape + (self.d,), np.float32)
        near = jnp.take(self.x, jnp.asarray(rows.ravel()), axis=0)
        return np.asarray(near).reshape(noise.shape) + noise

    def _warm_up(self, key):
        """Compile every shape the window uses, and no other: each write
        pad of the mix (then lookups that re-check ingests), and each
        flush size 1, 2, 4, .. max_batch, on queries outside the pool."""
        for pts in self.warm_pts:
            self.coal.ingest(pts)
            self.handed += len(pts)
            self.coal.flush()
        rows = np.asarray(jax.random.choice(key, self.n, (2 * self.max_batch,),
                                            replace=False))
        qs = np.asarray(jnp.take(self.x, jnp.asarray(rows), axis=0))
        tau = float(np.median(self.pool_tau)) * 1.001
        size, i = 1, 0
        while size <= self.max_batch:
            for _ in range(size):
                self.coal.submit(qs[i], tau)
                i += 1
            self.coal.flush()
            size *= 2
        jax.block_until_ready(self.coal.state)


# ------------------------------------------------------------ window ----
def _span(name: str):
    return jax.profiler.TraceAnnotation(name)


def serve_window(cell: Cell, seconds: float, drain_s: float = DRAIN_S,
                 clock=time.perf_counter, tracer=None):
    """The measured window (module docstring). Returns the raw record: per
    read its due and answer times, estimate and provenance, and how many
    handed-in points its answer could see. A ``tracer``
    (``trace.capture``) records the last ``trace.TRACE_SECONDS`` of the
    window, marked by the span ``bench/window``, and stops once every read
    is answered."""
    sch, coal = cell.sched, cell.coal
    n_r, n_g = len(sch.read_t), len(sch.ingest_t)
    cap = sch.outstanding                  # closed loop: reads in flight
    due_t = sch.read_t.copy()              # closed loop: set on admission
    done = np.full(n_r, np.nan)
    submitted = np.full(n_r, np.nan)
    est = np.zeros(n_r)
    prov: list = [None] * n_r
    nvis: list = []
    visible = np.zeros(n_r, np.int64)      # points visible to each answer
    reqs: dict = {}
    passes: list = []                      # (ms, reads answered) per pass
    nxt_r = nxt_g = 0
    ingest_pending = False
    stop_at = seconds + drain_s

    def admissible(now: float) -> bool:
        if nxt_r == n_r:
            return False
        if cap is None:
            return due_t[nxt_r] <= now and due_t[nxt_r] < seconds
        return now < seconds and len(reqs) < cap

    marked_from = 0.0 if tracer is None else \
        max(0.0, seconds - trace_mod.TRACE_SECONDS)
    marked = None                          # the open ``bench/window`` span
    t0 = clock()
    while True:
        now = clock() - t0
        closed = now >= seconds
        if marked is None and marked_from <= now < seconds:
            if tracer is not None:
                tracer.start()
            marked = _span("bench/window")
            marked.__enter__()
        if closed and marked is not None:
            marked.__exit__(None, None, None)
            marked, marked_from = None, math.inf
        more = nxt_r < n_r and (due_t[nxt_r] < seconds if cap is None
                                else not closed)
        if closed and not more and not reqs and not ingest_pending:
            break
        if now >= stop_at:
            break
        if admissible(now) or reqs or ingest_pending or \
                (nxt_g < n_g and sch.ingest_t[nxt_g] <= now):
            with _span("bench/flush"):
                with _span("bench/ingest"):
                    while nxt_g < n_g and sch.ingest_t[nxt_g] <= now:
                        coal.ingest(cell.ingest_pts[nxt_g])
                        cell.handed += cell.ingest_pts.shape[1]
                        nxt_g += 1
                        ingest_pending = True
                vis = cell.handed     # every answer of this pass sees them
                with _span("bench/submit"):
                    while admissible(now):
                        p = sch.read_pair[nxt_r]
                        q = sch.pair_query[p]
                        r = coal.submit(cell.pool_q[q],
                                        cell.pool_tau[q, sch.pair_target[p]])
                        reqs[r.rid] = (nxt_r, r)
                        if cap is not None:
                            due_t[nxt_r] = now
                        submitted[nxt_r] = now
                        nxt_r += 1
                res = coal.flush()
            ingest_pending = False
            t = clock() - t0
            passes.append((1e3 * (t - now), len(res)))
            for rid, val in res.items():
                i, r = reqs.pop(rid)
                done[i], est[i] = t, float(val)
                prov[i], visible[i] = val.provenance, vis
                if r.nvisited is not None:
                    nvis.append(r.nvisited)
            continue
        due = [stop_at if closed else seconds]
        if cap is None and nxt_r < n_r:
            due.append(due_t[nxt_r])
        if nxt_g < n_g:
            due.append(sch.ingest_t[nxt_g])
        wait = min(due) - (clock() - t0)
        if wait > 0:
            with _span("bench/sleep"):
                time.sleep(wait)
    gave_up = clock() - t0
    if tracer is not None and tracer.started:
        tracer.stop()
    # the reads offered in the window: due before its close (open loop),
    # or admitted before it (closed loop); they are a prefix of the reads
    offered = due_t < seconds if cap is None else ~np.isnan(submitted)
    return dict(due=due_t[offered], done=done[offered],
                submitted=submitted[offered], est=est[offered],
                prov=[p for p, w in zip(prov, offered) if w],
                visible=visible[offered], nvisited=nvis, gave_up=gave_up,
                longest_passes=sorted(passes, reverse=True)[:5])


def backlog_trend(due: np.ndarray, done: np.ndarray, seconds: float,
                  points: int = 200) -> dict:
    """Reads due and not yet answered, sampled over the window: its least
    squares slope (reads per second; above 0 the queue grows) and its mean
    over the first and the second half."""
    t = (np.arange(points) + 0.5) * seconds / points
    answered = np.sort(np.where(np.isnan(done), np.inf, done))
    backlog = (np.searchsorted(np.sort(due), t, side="right")
               - np.searchsorted(answered, t, side="right"))
    half = points // 2
    return {"slope_per_s": float(np.polyfit(t, backlog, 1)[0]),
            "mean_first_half": float(backlog[:half].mean()),
            "mean_second_half": float(backlog[half:].mean())}


# ------------------------------------------------------------- check ----
def check(cell: Cell, rec: dict, n_valid: int) -> tuple[np.ndarray, dict]:
    """Exact counts for every answer, and the numbers ``correct`` compares
    (each against the configuration's limit)."""
    sch = cell.sched
    answered = ~np.isnan(rec["done"])
    idx = np.nonzero(answered)[0]
    pairs = sch.read_pair[: len(rec["due"])][idx]
    qi, ti = sch.pair_query[pairs], sch.pair_target[pairs]
    qs, taus = cell.pool_q[qi], cell.pool_tau[qi, ti]
    truth = np.full(len(rec["due"]), np.nan)
    counts = reference.exact_counts(cell.corpus(), qs, taus)
    new = [p for p in cell.warm_pts] + list(cell.ingest_pts)
    if new:
        grown = np.concatenate(new, axis=0)
        counts = counts + reference.exact_counts(
            grown, qs, taus, limit=rec["visible"][idx])
    truth[idx] = counts

    # a hit must equal the probe that filled its entry: the last probe
    # (or stale refresh) of the same query and radius before it
    n = len(rec["due"])
    key_q = sch.pair_query[sch.read_pair[:n]]
    key_t = cell.pool_tau[key_q, sch.pair_target[sch.read_pair[:n]]]
    mismatch = 0
    last: dict = {}
    for i in idx[np.argsort(rec["done"][idx], kind="stable")]:
        key = (int(key_q[i]), float(key_t[i]))
        if rec["prov"][i] == "hit":
            mismatch += int(last.get(key) != rec["est"][i])
        else:
            last[key] = rec["est"][i]

    numbers = {"unanswered": float(np.sum(~answered)),
               "hit_mismatch": float(mismatch)}
    numbers.update(accuracy(rec["est"][idx], truth[idx],
                            cell.config["correct"].get("bands", {})))
    # every point handed to coal.ingest is in the index
    numbers["lost_points"] = float(cell.n + cell.handed - n_valid)
    return truth, numbers


def accuracy(est: np.ndarray, truth: np.ndarray, bands: dict) -> dict:
    """Q-error of every answer: ``qerror_<stat>`` over all of them and
    ``qerror_<stat>.<band>`` over those whose exact count lies in a band
    (``{name: [low, high]}``, high null for no bound), for the statistics
    gmean, p50, p90 and p95. A band with no answer has no numbers."""
    q = stats.qerror(est, truth)
    groups = {"": np.ones(len(q), bool)}
    for name, (lo, hi) in bands.items():
        groups[f".{name}"] = (truth >= lo) & (truth < (np.inf if hi is None
                                                        else hi))
    out = {}
    for suffix, sel in groups.items():
        if not sel.any():
            continue
        qq = q[sel]
        out[f"qerror_gmean{suffix}"] = float(np.exp(np.log(qq).mean()))
        for pct in (50, 90, 95):
            out[f"qerror_p{pct}{suffix}"] = float(np.percentile(qq, pct))
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when every limited number
    was read and is within its limit."""
    shown = {k: {"value": numbers.get(k), "limit": lim}
             for k, lim in limits.items()}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in shown.values())
    return ok, shown


# ------------------------------------------------------------- entry ----
def run(config: dict, traffic: dict, seed: int, seconds: float, trace: bool,
        e2e: list[dict], per_layer: list[dict], t_start: float,
        log=lambda s: print(s, file=sys.stderr, flush=True),
        drain_s: float = DRAIN_S, base: pathlib.Path = CHIP_DIR) -> dict:
    """Set up, serve one window, check; returns the result line's dict.
    The metric readers are found under ``base``."""
    cell = Cell(config, traffic, seed, seconds, log)
    dev = jax.devices()[0]
    # the set-up's objects (compiled programs, corpus, pool) live as long
    # as the server: keep them out of the collector, as in a long-running
    # process, so that a full collection of them (about 0.2 s) does not
    # stall the window at a random point
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    traced = trace_mod.capture() if trace else None
    with pauses, CompileClock() as clk:
        t_window = time.perf_counter()
        setup_s = t_window - t_start
        rec = serve_window(cell, seconds, drain_s, tracer=traced)
    gc.unfreeze()
    log(f"[window] wall_s={clk.wall_s:.3f} compiles={clk.compiles} "
        f"compile_s={clk.compile_s:.3f}")
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    cache_stats = dict(cell.coal.cache_stats)
    n_valid = int(cell.coal.state.n_valid)
    cell.coal = None                   # free the program's state first
    gc.collect()
    truth, numbers = check(cell, rec, n_valid)

    r = Run(seconds=seconds, setup_s=setup_s, due=rec["due"],
            done=rec["done"], est=rec["est"], prov=rec["prov"],
            nvisited=rec["nvisited"], truth=truth, cache_stats=cache_stats,
            gave_up=rec["gave_up"],
            trace=trace_mod.summarize(traced.path) if trace else None)
    if trace:
        traced.cleanup()
    late = 1e3 * (rec["submitted"] - rec["due"])[~np.isnan(rec["submitted"])]
    lat = 1e3 * (np.where(r.answered, r.done, r.gave_up) - r.due)
    window = {
        "offered": len(r.due), "answered": int(r.answered.sum()),
        "answered_in_window": int(np.sum(r.done <= seconds)),
        "backlog": backlog_trend(r.due, r.done, seconds),
        "generator_late_ms": {"p50": stats.percentile(late, 50),
                              "p99": stats.percentile(late, 99)},
        "latency_ms": {f"p{q}": stats.percentile(lat, q)
                       for q in (50, 90, 95, 99)},
        "longest_passes_ms_reads": rec["longest_passes"],
        "gc_pauses": pauses.summary(),
        "memory_peak_bytes": mem, "cache": cache_stats}
    log(f"[window] {json.dumps(window)}")
    log(f"[check] accuracy {json.dumps(numbers)}")

    metrics = {}
    for m in (per_layer if trace else e2e):
        v = load_metric(m["name"], base).read(r)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    correct, shown = verdict(numbers, config["correct"]["limits"])
    out = {"correct": correct, "attempted": int(len(r.due)),
           "failed": int(np.sum(~r.answered)), "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": mem}}
    if trace:
        out["device"]["busy_s"] = r.trace.busy_s
        out["device"]["window_s"] = r.trace.window_s
        out["breakdown"] = r.trace.breakdown()
    out["window"] = window
    out["accuracy"] = numbers
    out["checks"] = shown
    for k, v in shown.items():
        log(f"[check] {k}={v['value']} limit={v['limit']}")
    return out
