"""Mesh factories. A FUNCTION, not a module-level constant — importing this
module never touches jax device state (required by the dry-run contract).

Production target: TPU v5e, 256 chips/pod as a 16x16 (data, model) mesh;
multi-pod adds a leading DCN "pod" axis (2 pods = 512 chips). ``make_mesh``
is the elastic entry point: any (pod, data, model) shape whose product
matches the available device count works with the same sharding rules
(divisibility fallbacks degrade per-tensor annotations gracefully).
"""

from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...]):
    """Elastic mesh: 1D -> (data,), 2D -> (data, model), 3D -> (pod, data, model)."""
    axes = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}[len(shape)]
    return _mk(tuple(shape), axes)


def make_host_mesh():
    """All locally visible devices as a data-parallel mesh (tests/smoke)."""
    n = len(jax.devices())
    return _mk((n,), ("data",))


def parse_mesh_spec(spec: str):
    """``"dp,tp"`` (e.g. ``"2,4"``) -> a global (data, model) serving mesh.

    The product must equal the *global* device count — under
    ``jax.distributed`` that spans every process, so each host passes the
    same spec and gets the same mesh (device order is the global
    ``jax.devices()`` order, identical on all processes). ``"auto"``
    spreads all devices over the data axis."""
    if spec == "auto":
        return make_mesh((len(jax.devices()),))
    try:
        shape = tuple(int(x) for x in spec.split(","))
    except ValueError:
        raise SystemExit(
            f"--mesh {spec!r} is not 'dp,tp' integers (e.g. '2,4') or 'auto'")
    if len(shape) != 2 or any(s < 1 for s in shape):
        raise SystemExit(
            f"--mesh {spec!r}: exactly two positive factors dp,tp expected")
    n = len(jax.devices())
    if shape[0] * shape[1] != n:
        raise SystemExit(
            f"--mesh {spec!r}: dp*tp = {shape[0] * shape[1]} but "
            f"{n} global devices are visible")
    return make_mesh(shape)
