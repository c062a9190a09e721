"""Paper Fig. 2/3: offline construction latency + per-phase breakdown
(LSH index / neighbor table / optional PQ) vs the learned baseline's
training time."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import lsh, neighbors, pq as pqmod
from repro.core.config import ProberConfig
from repro.utils import compile_cache


def run(datasets=None):
    rows = []
    for name in datasets or common.DATASETS:
        ds = common.dataset(name)
        d = ds.x.shape[1]
        cfg = common.prober_cfg(True, d)
        key = jax.random.PRNGKey(0)

        t0 = time.time()
        idx = lsh.build_index(ds.x, cfg, key)
        jax.block_until_ready(idx.order)
        t_lsh = time.time() - t0

        t0 = time.time()
        nb = int(idx.n_buckets[0])
        codes = idx.bucket_codes[0][:nb]
        table = neighbors.build(codes, jnp.int32(nb), cfg.table_max_dist)
        jax.block_until_ready(table.dists)
        t_tab = time.time() - t0

        t0 = time.time()
        pq = pqmod.fit(ds.x, cfg, key)
        jax.block_until_ready(pq.codes)
        t_pq = time.time() - t0

        t0 = time.time()
        common.eval_mlp(ds)
        t_mlp = time.time() - t0

        rows.append({"dataset": name, "lsh_s": t_lsh, "table_s": t_tab,
                     "pq_s": t_pq, "mlp_train_s": t_mlp})
        print(f"[build] {name:9s} lsh={t_lsh:6.2f}s table={t_tab:6.2f}s "
              f"pq={t_pq:6.2f}s | mlp-train={t_mlp:6.2f}s")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    run()
