"""Distributed Dynamic Prober over a mesh of every visible device
(shard_map + psum): the dataset is partitioned, every shard probes locally,
cardinality is the psum of local estimates (DESIGN.md §4).

  PYTHONPATH=src python examples/distributed_estimate.py
  # several devices on a CPU host:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/distributed_estimate.py
"""
import jax
import jax.numpy as jnp

from repro.core import distributed as D, estimator as E
from repro.core.config import ProberConfig

n_dev = len(jax.devices())
print("devices:", n_dev)
mesh = jax.make_mesh((n_dev,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))

key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (16000, 64))
cfg = ProberConfig(n_tables=2, n_funcs=8, ring_budget=1024,
                   central_budget=1024, chunk=128)

state, params = D.build_sharded(x, cfg, key, mesh)
print(f"sharded index built: {n_dev} local partitions of",
      x.shape[0] // n_dev)

qs = x[:4] + 0.01
d2 = jnp.sort(jnp.sum((x - qs[0][None]) ** 2, axis=-1))
taus = jnp.sqrt(d2[jnp.array([10, 100, 500, 2000])]) + 1e-6
for mode in ("local", "sync"):
    ests = D.estimate_sharded(state, qs[:1].repeat(4, 0), taus, cfg, key,
                              mesh, mode=mode)
    for i, t in enumerate([10, 100, 500, 2000]):
        true = float(E.true_cardinality(x, qs[0], taus[i]))
        print(f"[{mode}] target={t:5d} estimate={float(ests[i]):8.1f} "
              f"true={true:6.0f}")
