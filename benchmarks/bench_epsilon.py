"""Paper Fig. 5: error-tolerance eps — accuracy/latency trade-off, incl. the
turning point after which smaller eps stops helping."""
from __future__ import annotations

from benchmarks import common
from repro.utils import compile_cache


def run(dataset: str = "sift", eps_grid=(0.1, 0.03, 0.01, 0.003, 0.001)):
    ds = common.dataset(dataset)
    d = ds.x.shape[1]
    rows = []
    for eps in eps_grid:
        out = common.eval_prober(ds, common.prober_cfg(False, d, eps=eps))
        rows.append({"eps": eps, "mean_qerror": out["stats"]["mean"],
                     "ms_per_query": out["ms_per_query"]})
        print(f"[eps] eps={eps:7.4f} meanQ={out['stats']['mean']:6.2f} "
              f"{out['ms_per_query']:7.2f} ms/query")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    run()
