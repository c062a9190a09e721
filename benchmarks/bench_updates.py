"""Paper Fig. 6/7 + Table 5: large-scale dynamic updates — plus the
DESIGN.md §10 amortized-streaming sweep.

Per dataset: 10% of the data builds the initial framework; the remaining
90% arrives as updates. We measure (a) one-shot update time vs a
from-scratch rebuild, (b) amortized incremental throughput (points/sec)
when the 90% streams through fixed-size chunks against the capacity-padded
recompile-free ingest step, (c) Q-error of the updated framework vs the
static build, (d) the learned baseline's degradation when its (frozen)
model is asked about the updated corpus — paper Table 5's failure mode.

``--stream`` (or ``stream_run()``) runs the acceptance sweep at N=64k:
amortized incremental points/sec vs the from-scratch alternative — a
rebuild after every chunk arrival, each at a NEW shape and therefore each
paying a fresh compile (exactly the growth cost the capacity-padded layout
avoids; DESIGN.md §10) — with post-update q-error side by side with a
fresh build over the same queries.
"""
from __future__ import annotations

import sys
import time

import jax

from benchmarks import common
from repro.core import baselines, estimator as E, updates
from repro.data import vectors as V
from repro.utils import compile_cache


def _timed(fn):
    t0 = time.time()
    out = fn()
    jax.block_until_ready(out)
    return out, time.time() - t0


def _stream(state, x_stream, cfg, chunk):
    """Feed ``x_stream`` through fixed-size update chunks; returns the final
    state and the wall time spent updating (excluding the first, compiling
    chunk — amortized steady-state throughput)."""
    n = x_stream.shape[0]
    state, t_warm = _timed(lambda: E.update(state, x_stream[:chunk], cfg))
    t0 = time.time()
    for i in range(chunk, n, chunk):
        state = E.update(state, x_stream[i:i + chunk], cfg)
    jax.block_until_ready(state.index.order)
    return state, time.time() - t0, t_warm


def _qerr_stats(st, cfg, queries, taus, cards, stride=2):
    errs = []
    for qi in range(queries.shape[0]):
        for t in range(0, taus.shape[1], stride):
            est = E.estimate(st, queries[qi], taus[qi, t], cfg,
                             jax.random.PRNGKey(qi * 31 + t))
            errs.append(common.qerror(float(est), float(cards[qi, t])))
    return common.qerror_stats(errs)


def stream_run(n: int = 65536, dim: int = 32, chunk: int = 4096,
               n_queries: int = 6):
    """DESIGN.md §10 acceptance sweep: amortized incremental update
    throughput vs from-scratch rebuild at N=64k."""
    key = jax.random.PRNGKey(0)
    x = V.make_corpus(key, n, dim)
    cfg = common.prober_cfg(False, dim)
    n0 = max((n // 10) // chunk * chunk, chunk)

    # capacity-padded stream: 10% initial, the rest in fixed chunks. The
    # first chunk compiles the ingest step; every later chunk reuses it.
    st0, t_init = _timed(
        lambda: E.build(x[:n0], cfg, key, capacity=updates.next_pow2(n)))
    st_upd, t_stream, t_warm = _stream(st0, x[n0:], cfg, chunk)
    assert int(st_upd.n_valid) == n
    streamed = n - n0 - chunk
    pts_inc = streamed / max(t_stream, 1e-9)

    # the from-scratch alternative for the SAME arrival stream: rebuild the
    # whole index after each chunk. Every rebuild has a new point count, so
    # every rebuild pays a fresh trace+compile — that (not the sort) is the
    # growth cost the recompile-free path amortizes away.
    t_rebuild_total = 0.0
    for end in range(n0 + 2 * chunk, n + 1, chunk):
        _, dt = _timed(lambda: E.build(x[:end], cfg, key))
        t_rebuild_total += dt
    pts_reb = streamed / max(t_rebuild_total, 1e-9)

    # reference: one final-shape rebuild, cold then compile-cached
    _, t_rebuild_cold = _timed(lambda: E.build(x, cfg, key))
    st_static, t_rebuild_warm = _timed(lambda: E.build(x, cfg, key))

    qs, taus, cards = V.paper_query_workload(jax.random.PRNGKey(1), x,
                                             n_queries)
    s_upd = _qerr_stats(st_upd, cfg, qs, taus, cards)
    s_static = _qerr_stats(st_static, cfg, qs, taus, cards)

    row = {"n": n, "chunk": chunk,
           "t_stream_s": t_stream, "t_first_chunk_s": t_warm,
           "t_rebuild_per_chunk_total_s": t_rebuild_total,
           "t_rebuild_once_cold_s": t_rebuild_cold,
           "t_rebuild_once_warm_s": t_rebuild_warm,
           "pts_per_s_incremental": pts_inc,
           "pts_per_s_rebuild_per_chunk": pts_reb,
           "speedup_vs_rebuild": pts_inc / max(pts_reb, 1e-9),
           "qerr_updated_mean": s_upd["mean"],
           "qerr_updated_p90": s_upd["p90"],
           "qerr_static_mean": s_static["mean"],
           "qerr_static_p90": s_static["p90"]}
    print(f"[updates/stream] N={n} chunk={chunk} "
          f"inc={pts_inc:,.0f} pts/s | rebuild-per-chunk={pts_reb:,.0f} "
          f"pts/s | speedup {row['speedup_vs_rebuild']:.1f}x | "
          f"meanQ updated={s_upd['mean']:.2f} static={s_static['mean']:.2f}")
    return [row]


def run(datasets=("sift", "glove"), chunk: int = 1024):
    rows = []
    for name in datasets:
        ds = common.dataset(name)
        d = ds.x.shape[1]
        cfg = common.prober_cfg(False, d)
        n = ds.x.shape[0]
        n0 = max(int(n * 0.1) // 4 * 4, 4)
        key = jax.random.PRNGKey(0)

        t0 = time.time()
        st0 = E.build(ds.x[:n0], cfg, key,
                      capacity=updates.next_pow2(n))
        jax.block_until_ready(st0.index.order)
        t_init = time.time() - t0

        # one-shot 90% update (paper Fig. 6 setting)
        st_upd, t_update = _timed(lambda: E.update(st0, ds.x[n0:], cfg))

        _, t_rebuild = _timed(lambda: E.build(ds.x, cfg, key))
        st_static, t_rebuild_warm = _timed(lambda: E.build(ds.x, cfg, key))

        # amortized streaming throughput over the same 90% (fixed chunks,
        # recompile-free in-capacity steps — DESIGN.md §10); the reference
        # is ONE compile-cached rebuild at the final shape, i.e. the most
        # charitable possible rebuild number (--stream measures the honest
        # rebuild-per-chunk baseline)
        st_s, t_stream, _ = _stream(st0, ds.x[n0:], cfg, chunk)
        streamed = max(n - n0 - chunk, 1)
        pts_inc = streamed / max(t_stream, 1e-9)
        pts_reb = n / max(t_rebuild_warm, 1e-9)

        s_upd = _qerr_stats(st_upd, cfg, ds.queries, ds.taus, ds.cards)
        s_static = _qerr_stats(st_static, cfg, ds.queries, ds.taus, ds.cards)

        # learned baseline: trained on the initial 10%, frozen, asked about
        # the full corpus (paper Table 5's setting)
        q_init, t_init_, c_init = V.paper_query_workload(
            jax.random.PRNGKey(1), ds.x[:n0], ds.queries.shape[0])
        m = baselines.fit_mlp(ds.x[:n0], q_init, t_init_, c_init,
                              jax.random.PRNGKey(2))
        errs = []
        for qi in range(ds.queries.shape[0]):
            for t in range(0, ds.taus.shape[1], 2):
                est = float(baselines.mlp_estimate(m, ds.queries[qi],
                                                   ds.taus[qi, t]))
                errs.append(common.qerror(est, float(ds.cards[qi, t])))
        s_mlp = common.qerror_stats(errs)

        rows.append({"dataset": name, "t_init_s": t_init,
                     "t_update_s": t_update, "t_rebuild_s": t_rebuild,
                     "pts_per_s_incremental": pts_inc,
                     "pts_per_s_rebuild": pts_reb,
                     "qerr_updated_mean": s_upd["mean"],
                     "qerr_static_mean": s_static["mean"],
                     "qerr_mlp_frozen_mean": s_mlp["mean"]})
        print(f"[updates] {name:9s} init={t_init:5.2f}s "
              f"update={t_update:5.2f}s rebuild={t_rebuild:5.2f}s | "
              f"stream {pts_inc:,.0f} pts/s vs rebuild {pts_reb:,.0f} pts/s | "
              f"meanQ updated={s_upd['mean']:.2f} static={s_static['mean']:.2f} "
              f"mlp-frozen={s_mlp['mean']:.2f}")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    if "--stream" in sys.argv:
        stream_run()
    else:
        run()
