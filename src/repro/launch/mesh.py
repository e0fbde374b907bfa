"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.

Topology (TPU v5e): single pod = 16 x 16 = 256 chips (data x model);
multi-pod = 2 pods x 256 = 512 chips with the "pod" axis crossing DCN.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
from jax.sharding import AxisType

# the chip the production meshes are laid out for (its roofline peaks are
# repro.roofline.constants.PEAKS[PRODUCTION_DEVICE_KIND])
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE "
            "importing jax (launch/dryrun.py does this)")
    return _auto_mesh(shape, axes, devices[:n])


def _auto_mesh(shape, axes, devices):
    # model code annotates activations with ``with_sharding_constraint``,
    # which only accepts Auto axes (make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh for multi-device unit tests (host platform, 4-8 devices)."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return _auto_mesh(shape, axes, devices[:n])
