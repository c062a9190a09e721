"""Elastic scaling (DESIGN.md §6): re-lower onto a different mesh extent and
reshard checkpointed state.

Because shardings are derived from logical rules (sharding/rules.py), any
mesh whose axis sizes divide the logical dims is valid — growing or shrinking
the ("pod","data") extent only changes the spec resolution. The elastic path
is therefore: checkpoint → build new mesh → re-derive specs → device_put the
restored host state → re-jit. ``plan_remesh`` picks the largest usable device
count (whole data-parallel replicas) after failures.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.launch.mesh import make_production_mesh  # noqa: F401  (re-export)


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    n_devices: int
    data: int
    model: int

    def make(self):
        return jax.make_mesh((self.data, self.model), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)


def plan_remesh(n_alive: int, model_parallel: int) -> MeshPlan:
    """Largest mesh using whole model-parallel groups on alive devices."""
    assert n_alive >= model_parallel, "fewer devices than one model replica"
    data = n_alive // model_parallel
    return MeshPlan(n_devices=data * model_parallel, data=data,
                    model=model_parallel)


def reshard(state, mesh, specs):
    """Host/old-mesh state -> new mesh placement."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(
            x, NamedSharding(mesh, s if isinstance(s, P) else P())),
        state, specs)
