"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh whose axes GSPMD partitions automatically: the serve step
    commits its inputs' shardings and lets the compiler place the rest
    (`jax.make_mesh` would default to explicit axes)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod prepends a 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(n_devices: int, model_size: int | None = None):
    """Mesh over the first `n_devices` devices of this host: ("data",
    "model") with the model axis `model_size` wide (default: every device on
    the model axis — the sharded-serving shape).

    On a TPU host these are its chips. A CPU host has one device unless
    `XLA_FLAGS=--xla_force_host_platform_device_count=N` is set before jax
    initializes. Validate up front with actionable errors instead of letting
    jax.make_mesh fail on an opaque reshape.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if model_size is None:
        model_size = n_devices
    if model_size < 1 or n_devices % model_size:
        raise ValueError(
            f"model_size={model_size} must divide n_devices={n_devices} "
            f"(mesh shape is (data={n_devices}//{model_size}, "
            f"model={model_size}))"
        )
    avail = jax.device_count()
    if avail < n_devices:
        raise RuntimeError(
            f"mesh wants {n_devices} devices but only {avail} "
            f"{jax.default_backend()} devices are visible (on a CPU host, "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_devices} before jax initializes)"
        )
    return _auto_mesh((n_devices // model_size, model_size), ("data", "model"))


def parse_mesh_spec(spec: str):
    """Mesh from a CLI spec string.

    "host:N"    — N devices of this host, all on the model axis
    "host:N@S"  — N host devices, model axis S wide (data axis N/S)
    "prod"      — the fixed 16x16 production pod
    "prod-pod"  — 2x16x16 multi-pod
    """
    s = spec.strip().lower()
    if s == "prod":
        return make_production_mesh()
    if s in ("prod-pod", "prod:pod"):
        return make_production_mesh(multi_pod=True)
    if s.startswith("host:"):
        body = s[len("host:"):]
        model: int | None = None
        if "@" in body:
            body, model_s = body.split("@", 1)
            try:
                model = int(model_s)
            except ValueError:
                raise ValueError(
                    f"bad mesh spec {spec!r}: model size {model_s!r} is not "
                    "an integer") from None
        try:
            n = int(body)
        except ValueError:
            raise ValueError(
                f"bad mesh spec {spec!r}: device count {body!r} is not an "
                "integer") from None
        return make_host_mesh(n, model)
    raise ValueError(
        f"unknown mesh spec {spec!r} — expected 'host:N', 'host:N@S', "
        "'prod', or 'prod-pod'"
    )


def mesh_axes(mesh) -> dict:
    """Role map for the sharding rules."""
    names = mesh.axis_names
    dp_axes = tuple(a for a in names if a in ("pod", "data"))
    return {
        "dp_axes": dp_axes,
        "data_size": math.prod(mesh.shape[a] for a in dp_axes) if dp_axes else 1,
        "model_axis": "model",
        "model_size": mesh.shape["model"],
    }
