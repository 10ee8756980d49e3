"""Mesh construction for the SPMD training backend; every mesh goes
through :func:`make_mesh`."""
from __future__ import annotations

import math
import os
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``axes`` (every axis ``AxisType.Auto``),
    built from the first ``prod(shape)`` of ``devices`` (default: all
    visible devices)."""
    assert len(shape) == len(axes), (shape, axes)
    devs = list(devices) if devices is not None else jax.devices()
    need = math.prod(shape)
    if len(devs) < need:
        raise ValueError(f"mesh {shape} over {axes} needs {need} devices, "
                         f"have {len(devs)}")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs[:need])


def make_host_pipeline_mesh(num_stages: int) -> jax.sharding.Mesh:
    """A 1-D ``("stage",)`` mesh over the first ``num_stages`` devices —
    the mesh the SPMD training backend (``Trainer(backend="spmd")``) runs
    on: one chip per stage on a TPU host.

    On the CPU, tests get several devices by setting ``XLA_FLAGS=
    --xla_force_host_platform_device_count=K`` before the first jax import
    (:func:`force_host_devices`); on an accelerator the devices are the
    chips, and a shortfall is an error, never a fallback to the CPU.
    """
    devs = jax.devices()
    if len(devs) < num_stages:
        raise RuntimeError(
            f"spmd backend needs one device per stage: num_stages="
            f"{num_stages} but only {len(devs)} {devs[0].platform} "
            f"device(s) are visible. On the CPU (JAX_PLATFORMS=cpu), force "
            "host devices with XLA_FLAGS="
            "--xla_force_host_platform_device_count=<K> before importing "
            "jax; otherwise reduce num_stages.")
    return make_mesh((num_stages,), ("stage",), devices=devs)


def force_host_devices(n: int) -> None:
    """Ask XLA for ``n`` host CPU devices — only when the process is held
    to the CPU (``JAX_PLATFORMS=cpu``), so that on an accelerator whose
    runtime fails to start the SPMD backend errors instead of quietly
    running on virtual CPU devices.

    Only effective before jax's FIRST backend query (jax locks the device
    count at initialization); a no-op when the flag is already present so
    an operator-set ``XLA_FLAGS`` always wins.
    """
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()
