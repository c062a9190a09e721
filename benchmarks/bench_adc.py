"""Paper Fig. 4: ADC vs exact distance computation — speedup vs
dimensionality (paper: ~1.6x, growing with d). Each row also exercises the
batched full-ADC-scan baseline (``adc_scan_estimate_batch`` -> the batched
Pallas kernel, DESIGN.md §9) on a code subset — on CPU the kernel runs in
interpret mode, so ``t_scan8_ms`` is a correctness/wiring check there, not
a perf claim; the kernel's bandwidth story is for TPU."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import baselines, pq as pqmod
from repro.core.config import ProberConfig
from repro.utils import compile_cache


def _time(fn, *args, reps=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def run(dims=(128, 304, 960, 1776), n: int = 20000):
    rows = []
    key = jax.random.PRNGKey(0)
    for d in dims:
        x = jax.random.normal(key, (n, d))
        q = x[0] + 0.1
        cfg = ProberConfig(pq_m=16, pq_kc=64, pq_iters=5)
        pq = pqmod.fit(x, cfg, key)
        lut = pqmod.adc_table(pq, q)

        exact = jax.jit(lambda xx, qq: jnp.sum((xx - qq[None]) ** 2, -1))
        adc = jax.jit(pqmod.adc_distance)
        t_exact = _time(exact, x, q)
        t_adc = _time(adc, lut, pq.codes)
        # batched multi-query scan through the Pallas kernel (Q=8, code
        # subset: interpret-mode execution on CPU is Python-speed)
        sub = pqmod.PQIndex(centroids=pq.centroids, codes=pq.codes[:2048],
                            counts=pq.counts, resid=pq.resid[:2048],
                            n_valid=jnp.int32(2048))
        qs8 = x[:8] + 0.1
        taus8 = jnp.full((8,), jnp.sqrt(jnp.mean(jnp.sum(x[:64] ** 2, -1))))
        t_scan = _time(baselines.adc_scan_estimate_batch, sub, qs8, taus8,
                       reps=3)
        rows.append({"dim": d, "t_exact_ms": 1e3 * t_exact,
                     "t_adc_ms": 1e3 * t_adc,
                     "t_scan8_ms": 1e3 * t_scan,
                     "speedup": t_exact / t_adc})
        print(f"[adc] d={d:5d} exact={1e3*t_exact:7.3f}ms "
              f"adc={1e3*t_adc:7.3f}ms speedup={t_exact/t_adc:5.2f}x "
              f"scan8={1e3*t_scan:7.1f}ms")
    return rows


if __name__ == "__main__":
    compile_cache.enable()
    run()
