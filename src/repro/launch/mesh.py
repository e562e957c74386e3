"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches JAX device state.  The dry-run forces 512
host devices via XLA_FLAGS before any JAX import; smoke tests and benchmarks
see the real single device.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import Mesh

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(n_devices: Optional[int] = None, *,
                   multi_pod: bool = False) -> Mesh:
    """A small mesh matching whatever host devices exist (unit tests)."""
    n = n_devices or len(jax.devices())
    if multi_pod:
        assert n % 2 == 0
        per_pod = n // 2
        d = _best_split(per_pod)
        return _mesh((2, d, per_pod // d), ("pod", "data", "model"))
    d = _best_split(n)
    return _mesh((d, n // d), ("data", "model"))


def _best_split(n: int) -> int:
    r = int(math.sqrt(n))
    while n % r:
        r -= 1
    return r


def _mesh(shape, axes) -> Mesh:
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)}; the "
            f"dry-run entry point must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"BEFORE importing jax.")
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(shape))


#: TPU v5e hardware constants used by the roofline analysis (per chip).
TPU_V5E = {
    "peak_bf16_flops": 197e12,       # FLOP/s
    "hbm_bandwidth": 819e9,          # B/s
    "ici_bandwidth": 200e9,          # B/s per chip (1,600 Gbit/s)
    "hbm_bytes": 16 * 2**30,         # 16 GiB
}
