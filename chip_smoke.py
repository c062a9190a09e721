"""Bring-up smoke run of the estimator's serving path on TPU chips.

    python chip_smoke.py [--seed S]              # one chip: the serving path
    python chip_smoke.py --chips 4 [--seed S]    # four chips: the sharded path

One chip: a SIFT1M-shaped corpus (ANN-Benchmarks ``sift-128-euclidean``:
1,000,000 x 128, L2) is generated from the seed, built with
``estimator.build`` at capacity 2^20 under ``serve_cfg(128)``, and served
through ``CardinalityCoalescer`` with the estimate cache on: coalesced
flushes of the dataset's paper-protocol (q, tau) requests, a repeat flush
that the cache answers, one 256-point ingest chunk (the recompile-free
in-capacity step) and a flush after it. A small exact-mode build then
checks the prober against the exact count on the chip.

Four chips (``--chips 4``) runs only the sharded path:
``distributed.build_sharded`` of a 4,000,000 x 128 corpus at capacity
2^22, one ``update_sharded`` chunk, and ``CardinalityCoalescer(mesh=...)``
flushes in the ``local`` and ``sync`` stopping modes, plus the same
exact-mode check across the mesh.

Every estimate is compared with ``estimator.true_cardinality`` computed on
the device(s). Each phase prints one line of set-up and sanity figures
(not benchmark metrics). The last line of standard output is
``{"ok": true, "device": {...}}``; without it the script exits non-zero,
which it does when the first device is not a TPU or any check fails.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import qerror, qerror_stats, serve_cfg  # noqa: E402
from repro.core import distributed as D, estimator as E  # noqa: E402
from repro.core.config import ProberConfig  # noqa: E402
from repro.data import vectors  # noqa: E402
from repro.serve.engine import CardinalityCoalescer  # noqa: E402
from repro.utils import compile_cache  # noqa: E402

# Requests per flush. Compiled for one v5e chip at capacity 2^20 under
# serve_cfg(128), the flush step fits at Q=8 (1.4 GiB of temporaries);
# at Q=16, 32 and 64 the compiler refuses it (16.06, 16.21 and 16.34 GB
# of 15.75 GB of HBM): per-lane bucket-axis state grows with the capacity.
FLUSH = 8
INGEST = 256                 # one serving ingest chunk (cfg.ingest_chunk)
CACHE = 1024                 # estimate-cache entries
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CheckFailed(Exception):
    """A smoke check did not hold."""


def check(ok, what: str):
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Sums the backend compile seconds JAX reports while it is open."""

    def __init__(self):
        self.secs = 0.0

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.secs += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def report(phase: str, clock: CompileClock | None = None, **fields):
    if clock is not None:
        fields = {"wall_s": round(clock.wall, 3),
                  "compile_s": round(clock.secs, 3), **fields}
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def bytes_in_use(devices) -> list:
    stats = [d.memory_stats() for d in devices]
    return [s["bytes_in_use"] if s else None for s in stats]


def qerror_summary(ests, truth) -> str:
    s = qerror_stats([qerror(float(e), float(t))
                      for e, t in zip(ests, truth)])
    return f"mean={s['mean']:.3f}/p90={s['p90']:.3f}/max={s['max']:.3f}"


@jax.jit
def _truth(x, n_valid, qs, taus):
    """Exact counts for (qs[i], taus[i]) over a capacity-padded corpus —
    one (N, d) pass per request, so memory stays that of one request."""
    return jax.lax.map(
        lambda a: E.true_cardinality(x, a[0], a[1], n_valid), (qs, taus))


@jax.jit
def _sharded_truth(x, n_valid, qs, taus):
    """:func:`_truth` over a sharded state: x (S, C/S, d), n_valid (S,).
    The shard axis stays partitioned over the mesh; one sum crosses it."""
    per_shard = jax.vmap(_truth, in_axes=(0, 0, None, None))
    return per_shard(x, n_valid, qs, taus).sum(axis=0)


def host_count(points: np.ndarray, qs, taus) -> np.ndarray:
    """Independent host count of ``points`` within each (q, tau)."""
    d2 = ((points[None, :, :].astype(np.float64) -
           np.asarray(qs, np.float64)[:, None, :]) ** 2).sum(-1)
    return (d2 <= np.asarray(taus, np.float64)[:, None] ** 2).sum(-1)


def check_estimates(phase: str, ests):
    ests = np.asarray(ests, np.float64)
    check(np.all(np.isfinite(ests)), f"{phase}: non-finite estimate {ests}")
    check(np.all(ests >= 0), f"{phase}: negative estimate {ests}")


def ingest_points(q, seed: int, d: int) -> np.ndarray:
    """INGEST new points tightly around query ``q``: they land in its
    central bucket, so cache entries that probed it must go stale."""
    rng = np.random.default_rng(seed)
    return (np.asarray(q, np.float32)[None, :] +
            1e-3 * rng.standard_normal((INGEST, d)).astype(np.float32))


def exact_cfg() -> ProberConfig:
    """eps=0 and s1=1 with budgets above the corpus size: every ring is
    walked whole, so the estimate must equal the exact count."""
    return ProberConfig(n_tables=1, n_funcs=8, ring_budget=4096,
                        central_budget=4096, chunk=256, eps=0.0, s1=1.0,
                        max_visit=1 << 20)


def exact_phase(x, seed: int, build, estimators: dict) -> None:
    """Small exact-mode reference on the device(s): 4,096 corpus points
    and paper-protocol queries over them. ``build(xs, cfg, key)`` makes
    the index; each ``estimators[name](state, qs, taus, cfg, key)`` is a
    path under test and must return the exact counts."""
    key = jax.random.PRNGKey(seed + 2)
    xs = x[:4096]
    qs, taus, cards = vectors.paper_query_workload(key, xs, FLUSH)
    cards = np.asarray(cards)
    state = build(xs, exact_cfg(), key)
    for name, estimate in estimators.items():
        with CompileClock() as clk:
            ests = np.stack([np.asarray(estimate(state, qs, taus[:, t],
                                                 exact_cfg(), key))
                             for t in range(taus.shape[1])], axis=1)
        check_estimates(name, ests.ravel())
        err = float(np.max(np.abs(ests - cards)))
        report(name, clk, n=xs.shape[0], requests=ests.size,
               max_abs_err=round(err, 5))
        check(err < 1e-2, f"{name}: |est - exact| = {err} >= 1e-2")


def one_chip(seed: int, scale: float = 25.0, capacity: int = 2 ** 20,
             n_flushes: int = 4) -> None:
    """The serving path on one device (module docstring)."""
    dev = jax.devices()[0]
    key = jax.random.PRNGKey(seed)
    k_data, k_build, k_serve = jax.random.split(key, 3)
    cfg = serve_cfg(128)

    with CompileClock() as clk:
        ds = vectors.load("sift", key=k_data, scale=scale)
        jax.block_until_ready(ds.cards)
    n, d = ds.x.shape
    report("data", clk, corpus=f"{n}x{d}", requests=ds.taus.size)

    with CompileClock() as clk:
        state = E.build(ds.x, cfg, k_build, capacity=capacity)
        jax.block_until_ready(state)
    nv = int(state.n_valid)
    report("build", clk, n_valid=nv, capacity=state.capacity,
           bytes_in_use=bytes_in_use([dev])[0])
    check(nv == n and state.capacity == capacity, "build: wrong live count")

    qs_all = np.asarray(ds.queries)
    taus_all = np.asarray(ds.taus)
    pairs = np.random.default_rng(seed).permutation(ds.taus.size)
    batches = [pairs[i * FLUSH:(i + 1) * FLUSH] for i in range(n_flushes)]

    def requests(batch):
        qi, ti = np.divmod(batch, taus_all.shape[1])
        return qs_all[qi], taus_all[qi, ti]

    coal = CardinalityCoalescer(state, cfg, k_serve, max_batch=FLUSH,
                                cache_size=CACHE)

    def flush(phase, batch):
        qs, taus = requests(batch)
        with CompileClock() as clk:
            reqs = [coal.submit(q, t) for q, t in zip(qs, taus)]
            res = coal.flush()
        ests = np.asarray([res[r.rid] for r in reqs])
        prov = [res[r.rid].provenance for r in reqs]
        st = coal.state
        truth = np.asarray(_truth(st.x, st.n_valid, jnp.asarray(qs),
                                  jnp.asarray(taus)))
        check_estimates(phase, ests)
        report(phase, clk, requests=len(reqs), qerror=qerror_summary(
            ests, truth), hits=prov.count("hit"),
            stale=prov.count("stale-refresh"))
        return ests, prov, truth

    first = None
    for i, batch in enumerate(batches):
        ests, prov, truth = flush(f"flush{i}", batch)
        check(prov == ["probe"] * len(batch), f"flush{i}: cache hit {prov}")
        qi, ti = np.divmod(batch, taus_all.shape[1])
        diff = np.abs(truth - np.asarray(ds.cards)[qi, ti])
        check(diff.max() <= 1, f"flush{i}: exact count on the built state "
              f"disagrees with the dataset's by {diff.max()}")
        if first is None:
            first = (ests, truth)

    ests, prov, _ = flush("repeat", batches[0])
    check(prov == ["hit"] * FLUSH, f"repeat: not served by the cache {prov}")
    check(np.array_equal(ests, first[0]), "repeat: cache hit != its probe")

    q0 = requests(batches[0])[0][0]
    new = ingest_points(q0, seed, d)
    with CompileClock() as clk:
        coal.ingest(new)
        coal.apply_ingest()
        jax.block_until_ready(coal.state)
    nv2 = int(coal.state.n_valid)
    report("ingest", clk, points=INGEST, n_valid=nv2)
    check(nv2 == n + INGEST, f"ingest: live count {nv2} != {n + INGEST}")

    ests, prov, truth = flush("after_ingest", batches[0])
    qs, taus = requests(batches[0])
    grown = host_count(new, qs, taus)
    check(np.abs(truth - first[1] - grown).max() <= 1,
          "after_ingest: exact count did not grow by the ingested points")
    near_q0 = [p for p, q in zip(prov, qs) if np.array_equal(q, q0)]
    check(all(p == "stale-refresh" for p in near_q0),
          f"after_ingest: stale entries served as hits {prov}")

    exact_phase(ds.x, seed, E.build, {"exact": E.estimate_batch})


def four_chips(seed: int, scale: float = 100.0, capacity: int = 2 ** 22,
               n_devices: int = 4) -> None:
    """The sharded path on ``n_devices`` devices (module docstring)."""
    devs = jax.devices()[:n_devices]
    check(len(devs) == n_devices, f"needs {n_devices} devices, has "
          f"{len(jax.devices())}")
    mesh = jax.make_mesh((n_devices,), ("data",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(seed)
    k_data, k_build, k_serve = jax.random.split(key, 3)
    cfg = serve_cfg(128)

    with CompileClock() as clk:
        ds = vectors.load("sift", key=k_data, n_queries=8, scale=scale)
        jax.block_until_ready(ds.cards)
    n, d = ds.x.shape
    report("data", clk, corpus=f"{n}x{d}", requests=ds.taus.size)

    with CompileClock() as clk:
        state, _ = D.build_sharded(ds.x, cfg, k_build, mesh,
                                   capacity=capacity)
        jax.block_until_ready(state)
    report("build_sharded", clk, shards=n_devices,
           n_valid=np.asarray(state.n_valid).tolist(),
           bytes_in_use=bytes_in_use(devs))
    check(int(np.sum(state.n_valid)) == n, "build_sharded: live count")

    qs_all, taus_all = np.asarray(ds.queries), np.asarray(ds.taus)
    cards = np.asarray(ds.cards)
    x_small = ds.x[:4096]
    del ds                                  # the unsharded corpus
    report("corpus_dropped", bytes_in_use=bytes_in_use(devs))

    new = ingest_points(qs_all[0], seed, d)
    with CompileClock() as clk:
        state, nv = D.update_sharded(state, new, cfg, mesh)
        jax.block_until_ready(state)
    report("update_sharded", clk, points=INGEST, n_valid=nv.tolist())
    check(int(nv.sum()) == n + INGEST, "update_sharded: live count")

    qi, ti = np.divmod(np.arange(taus_all.size), taus_all.shape[1])
    qs, taus = qs_all[qi], taus_all[qi, ti]
    truth = np.asarray(_sharded_truth(state.x, state.n_valid,
                                      jnp.asarray(qs), jnp.asarray(taus)))
    grown = host_count(new, qs, taus)
    check(np.abs(truth - cards[qi, ti] - grown).max() <= 1,
          "sharded exact count != dataset count + ingested points")

    for mode in ("local", "sync"):
        coal = CardinalityCoalescer(state, cfg, k_serve, max_batch=FLUSH,
                                    mesh=mesh, mode=mode)
        with CompileClock() as clk:
            reqs = [coal.submit(q, t) for q, t in zip(qs, taus)]
            res = coal.flush()
        ests = np.asarray([res[r.rid] for r in reqs])
        check_estimates(mode, ests)
        report(f"flush_{mode}", clk, requests=len(reqs),
               flushes=-(-len(reqs) // FLUSH),
               qerror=qerror_summary(ests, truth),
               bytes_in_use=bytes_in_use(devs))

    exact_phase(x_small, seed,
                lambda xs, c, k: D.build_sharded(xs, c, k, mesh)[0],
                {f"exact_{m}": functools.partial(D.estimate_sharded,
                                                 mesh=mesh, mode=m)
                 for m in ("local", "sync")})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: the first device is {dev.platform!r}",
              file=sys.stderr)
        return 1
    report("setup", compile_cache=compile_cache.enable(),
           device=dev.device_kind, count=len(jax.devices()))
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
