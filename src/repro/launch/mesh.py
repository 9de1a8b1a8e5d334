"""Production mesh builders (functions — importing never touches jax device
state; jax locks the device count on first backend init)."""

from __future__ import annotations

import jax


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto: the partitioner, not the
    program, places what the shardings leave open. ``devices`` defaults to
    all of the process's devices."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16x16 = 256 chips/pod; 2 pods = 512 for multi-pod.

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod —
    "pod" composes with "data" for DP (default) or acts as the pipeline
    stage axis when PP is enabled (distributed/pipeline.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, *, multi_pod: bool = False,
                   devices=None):
    """Small mesh over ``devices``, else over however many (fake) devices
    the process has: (n / 2, 2) ("data", "model") from four devices on."""
    n = len(devices) if devices is not None else n_devices or len(jax.devices())
    if multi_pod:
        assert n % 2 == 0
        model = 2 if n >= 8 else 1
        return make_mesh((2, n // 2 // model, model), ("pod", "data", "model"),
                         devices)
    model = 2 if n >= 4 and n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"), devices)
