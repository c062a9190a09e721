"""Paper Table 3: Q-error distribution per dataset × method."""
from __future__ import annotations

import jax

from benchmarks import common
from repro.utils import compile_cache


def run(datasets=None):
    rows = []
    for name in datasets or common.DATASETS:
        ds = common.dataset(name)
        d = ds.x.shape[1]
        methods = {
            "DynamicProber": lambda: common.eval_prober(
                ds, common.prober_cfg(False, d)),
            "DynamicProber-PQ": lambda: common.eval_prober(
                ds, common.prober_cfg(True, d)),
            "Sampling1%": lambda: common.eval_sampling(ds, 0.01),
            "MLP-lite": lambda: common.eval_mlp(ds),
        }
        for meth, fn in methods.items():
            out = fn()
            s = out["stats"]
            rows.append({"dataset": name, "method": meth, **s})
            print(f"[qerror] {name:9s} {meth:16s} mean={s['mean']:7.2f} "
                  f"p90={s['p90']:7.2f} p95={s['p95']:7.2f} "
                  f"p99={s['p99']:8.2f} max={s['max']:9.2f}")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    run()
