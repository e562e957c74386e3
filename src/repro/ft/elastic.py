"""Elastic reconfiguration: shrink/regrow the data axis after host loss.

The checkpoint format is mesh-agnostic (whole logical arrays restored through
``device_put`` with the NEW mesh's shardings), so elasticity reduces to:
  1. pick the largest viable data-axis size for the surviving hosts,
  2. rebuild the mesh,
  3. restore the last checkpoint under the new shardings,
  4. rescale the data pipeline (global batch keeps its size by growing the
     per-host microbatch, or shrinks if configured).
"""
from __future__ import annotations

import dataclasses

import jax

from jax.sharding import AxisType

from ..parallel import sharding as shd


@dataclasses.dataclass(frozen=True)
class ElasticPlan:
    old_shape: dict
    new_shape: dict
    lost_hosts: int
    batch_policy: str          # "keep_global" | "shrink"
    note: str = ""


def plan_reshard(mesh, n_failed_hosts: int, devices_per_host: int = 4,
                 batch_policy: str = "keep_global") -> ElasticPlan:
    """Largest data-axis size that fits the surviving device count while
    keeping the model axis intact (TP degree is architectural)."""
    old = dict(mesh.shape)
    model = old.get("model", 1)
    pod = old.get("pod", 1)
    total = 1
    for v in old.values():
        total *= v
    surviving = total - n_failed_hosts * devices_per_host
    new_data = surviving // (model * pod)
    if new_data < 1:
        raise RuntimeError("not enough devices for one data replica")
    new = dict(old)
    new["data"] = new_data
    return ElasticPlan(old_shape=old, new_shape=new,
                       lost_hosts=n_failed_hosts,
                       batch_policy=batch_policy,
                       note=f"{surviving}/{total} devices")


def build_mesh(plan: ElasticPlan):
    names = tuple(plan.new_shape.keys())
    shape = tuple(plan.new_shape.values())
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()[:need]
    return jax.make_mesh(shape, names, devices=devs,
                         axis_types=(AxisType.Auto,) * len(shape))


def reshard_tree(tree, spec_tree, new_mesh, rules=None):
    """device_put every leaf under the new mesh's shardings."""
    from ..models.spec import is_spec

    def put(s, x):
        sh = shd.named_sharding(s.logical, new_mesh, rules, s.shape)
        return jax.device_put(x, sh)
    return jax.tree.map(put, spec_tree, tree, is_leaf=is_spec)
