"""Production mesh definition.

Defined as a FUNCTION (not a module-level constant) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain 512 placeholder devices.
"""

from __future__ import annotations

import jax  # noqa: F401  (kept for callers poking jax.devices)

from repro.core import jax_compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax_compat.make_mesh(shape, axes)


def make_debug_mesh():
    """1x1 mesh for single-device tests of the same code paths."""
    return jax_compat.make_mesh((1, 1), ("data", "model"))


def parse_mesh_spec(spec: str) -> tuple[int, int]:
    """Parse a ``DxM`` serving-mesh spec ("2x1", "4x2") into (data, model).

    ``data`` counts independent page-pool shards (each owns a slice of the
    request stream); ``model`` counts tensor-parallel head groups within a
    shard.
    """
    parts = str(spec).lower().split("x")
    try:
        dims = [int(p) for p in parts]
    except ValueError:
        dims = []
    if len(dims) != 2 or any(d < 1 for d in dims):
        raise ValueError(
            f"bad mesh spec {spec!r}: expected DxM with positive integers, "
            "e.g. '2x1' (2 data shards) or '2x2' (2 shards x 2-way heads)"
        )
    return dims[0], dims[1]


def make_serving_mesh(spec: str = "1x1"):
    """Build a (data, model) mesh for sharded paged serving.

    Raises with a remediation hint when the host exposes fewer devices than
    the spec needs — on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes its backends; on an accelerator the mesh needs that many
    real chips.
    """
    data, model = parse_mesh_spec(spec)
    devices = jax.devices()
    need = data * model
    if len(devices) < need:
        platform = devices[0].platform
        if platform == "cpu":
            hint = (
                "on CPU export XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need} before "
                "jax initializes (or pick a smaller mesh)"
            )
        else:
            hint = (
                f"{devices[0].device_kind} chips are real devices — run on "
                f"a host with {need} of them (or pick a smaller mesh)"
            )
        raise ValueError(
            f"mesh {spec!r} needs {need} devices but only {len(devices)} "
            f"{platform} devices are visible; {hint}"
        )
    return jax_compat.make_mesh((data, model), ("data", "model"))
