"""DESIGN.md §4 acceptance sweep: sharded dynamic serving on forced host
devices — incremental-ingest throughput and q-error vs shard count, with
both distributed stopping modes (local vs sync) side by side.

Per shard count S: build the capacity-padded sharded index on 10% of an
N=64k corpus, stream the remaining 90% through fixed-size chunks routed
round-robin to the shards (ONE jitted shard_map ingest step per chunk,
recompile-free in capacity — DESIGN.md §10 extended to the sharded index),
then measure estimation q-error through ``estimate_sharded`` in ``local``
and ``sync`` mode. S=1 is the plain single-device capacity-padded path
(PR-2's bench_updates stream), giving the in-process reference the sharded
aggregates and q-errors are compared against.

Standalone (forces its own XLA host device count, so not part of
``benchmarks.run``'s in-process suite):

  PYTHONPATH=src python -m benchmarks.bench_sharded          # sweep 1,2,4,8
  PYTHONPATH=src python -m benchmarks.bench_sharded --quick  # 1 and 8 only
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys                                              # noqa: E402
import time                                             # noqa: E402

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from benchmarks import common                           # noqa: E402
from repro.core import distributed as D, estimator as E, updates  # noqa: E402
from repro.data import vectors as V                     # noqa: E402
from repro.utils import compile_cache                   # noqa: E402


def _stream_single(x, cfg, key, n0, chunk):
    """S=1 reference: the plain capacity-padded single-device stream."""
    st = E.build(x[:n0], cfg, key, capacity=updates.next_pow2(x.shape[0]))
    jax.block_until_ready(st.index.order)
    t0 = time.time()
    st = E.update(st, x[n0:n0 + chunk], cfg)            # compiling chunk
    jax.block_until_ready(st.index.order)
    t_warm = time.time() - t0
    t0 = time.time()
    for i in range(n0 + chunk, x.shape[0], chunk):
        st = E.update(st, x[i:i + chunk], cfg)
    jax.block_until_ready(st.index.order)
    return st, time.time() - t0, t_warm


def _stream_sharded(x, cfg, key, n0, chunk, mesh):
    st, _ = D.build_sharded(x[:n0], cfg, key, mesh,
                            capacity=updates.next_pow2(x.shape[0]))
    jax.block_until_ready(st.index.order)
    x_np = np.asarray(x)
    t0 = time.time()
    st, nv = D.update_sharded(st, x_np[n0:n0 + chunk], cfg, mesh)
    jax.block_until_ready(st.index.order)
    t_warm = time.time() - t0
    t0 = time.time()
    for i in range(n0 + chunk, x.shape[0], chunk):
        st, nv = D.update_sharded(st, x_np[i:i + chunk], cfg, mesh,
                                  n_valid=nv)
    jax.block_until_ready(st.index.order)
    return st, time.time() - t0, t_warm


def _qerr_single(st, cfg, queries, taus, cards, key, stride=2):
    errs = []
    for qi in range(queries.shape[0]):
        cols = list(range(0, taus.shape[1], stride))
        qrep = jnp.tile(queries[qi][None], (len(cols), 1))
        ests = E.estimate_batch(st, qrep, taus[qi, jnp.asarray(cols)], cfg,
                                jax.random.fold_in(key, qi))
        errs += [common.qerror(float(ests[j]), float(cards[qi, t]))
                 for j, t in enumerate(cols)]
    return common.qerror_stats(errs)


def _qerr_sharded(st, cfg, queries, taus, cards, key, mesh, mode, stride=2):
    errs = []
    for qi in range(queries.shape[0]):
        cols = list(range(0, taus.shape[1], stride))
        qrep = jnp.tile(queries[qi][None], (len(cols), 1))
        ests = D.estimate_sharded(st, qrep, taus[qi, jnp.asarray(cols)], cfg,
                                  jax.random.fold_in(key, qi), mesh,
                                  mode=mode)
        errs += [common.qerror(float(ests[j]), float(cards[qi, t]))
                 for j, t in enumerate(cols)]
    return common.qerror_stats(errs)


def run(n: int = 65536, dim: int = 32, chunk: int = 4096,
        n_queries: int = 6, shard_counts=(1, 2, 4, 8)):
    key = jax.random.PRNGKey(0)
    x = V.make_corpus(key, n, dim)
    cfg = common.prober_cfg(False, dim)
    n0 = max((n // 10) // chunk * chunk, chunk)
    streamed = n - n0 - chunk            # excludes the compiling first chunk
    qs, taus, cards = V.paper_query_workload(jax.random.PRNGKey(1), x,
                                             n_queries)
    avail = len(jax.devices())
    rows = []
    for s in shard_counts:
        if s > avail:
            print(f"[sharded] skip S={s}: only {avail} devices")
            continue
        if s == 1:
            st, t_stream, t_warm = _stream_single(x, cfg, key, n0, chunk)
            assert int(jax.device_get(st.index.n_valid)) == n
            q_local = q_sync = _qerr_single(st, cfg, qs, taus, cards, key)
        else:
            mesh = jax.make_mesh((s,), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,),
                                 devices=jax.devices()[:s])
            st, t_stream, t_warm = _stream_sharded(x, cfg, key, n0, chunk,
                                                   mesh)
            nv = np.asarray(jax.device_get(st.index.n_valid))
            assert int(nv.sum()) == n, nv
            q_local = _qerr_sharded(st, cfg, qs, taus, cards, key, mesh,
                                    "local")
            q_sync = _qerr_sharded(st, cfg, qs, taus, cards, key, mesh,
                                   "sync")
        pts = streamed / max(t_stream, 1e-9)
        rows.append({"shards": s, "n": n, "chunk": chunk,
                     "t_stream_s": t_stream, "t_first_chunk_s": t_warm,
                     "pts_per_s_ingest": pts,
                     "qerr_local_mean": q_local["mean"],
                     "qerr_local_p90": q_local["p90"],
                     "qerr_sync_mean": q_sync["mean"],
                     "qerr_sync_p90": q_sync["p90"]})
        print(f"[sharded] S={s} ingest={pts:,.0f} pts/s "
              f"(first-chunk {t_warm:.2f}s) | meanQ local="
              f"{q_local['mean']:.3f} sync={q_sync['mean']:.3f}")
    base = rows[0]
    for r in rows[1:]:
        r["ingest_speedup_vs_1dev"] = \
            r["pts_per_s_ingest"] / max(base["pts_per_s_ingest"], 1e-9)
        r["qerr_local_vs_1dev"] = \
            r["qerr_local_mean"] / max(base["qerr_local_mean"], 1e-9)
    if len(rows) > 1:
        last = rows[-1]
        print(f"[sharded] S={last['shards']} vs single-device: ingest "
              f"{last['ingest_speedup_vs_1dev']:.2f}x, meanQ ratio "
              f"{last['qerr_local_vs_1dev']:.3f}")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    if "--quick" in sys.argv:
        run(shard_counts=(1, 8))
    else:
        run()
