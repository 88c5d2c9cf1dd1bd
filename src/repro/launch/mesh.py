"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device,
while the dry-run initialises 512 placeholder devices before calling in.
Every axis is ``AxisType.Auto``: shardings propagate through GSPMD.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips; two pods add a leading
    `pod` axis (512 chips).  DP/FSDP runs on (pod, data); TP/EP/SP on model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever local devices exist (tests, examples).

    Raises a descriptive :class:`RuntimeError` (NOT a bare assert) when
    the process does not expose enough devices, so multi-device tests can
    ``pytest.skip`` on the message instead of erroring.  On CPU, force
    extra host devices with::

        XLA_FLAGS=--xla_force_host_platform_device_count=N

    set in the environment BEFORE jax is imported.
    """
    n = data * model
    devs = jax.devices()[:n]
    if len(devs) != n:
        raise RuntimeError(
            f"make_host_mesh(data={data}, model={model}) needs {n} "
            f"devices but this process sees {len(jax.devices())}; on CPU "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            "before importing jax (subprocess-style, see "
            "tests/test_mesh_serving.py and docs/sharding.md)")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devs)


def mesh_chips(mesh: Mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
