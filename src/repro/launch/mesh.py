"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The single-pod mesh is one
TPU v5e pod (16 x 16 = 256 chips); the multi-pod mesh adds an outer
``pod`` axis (2 pods = 512 chips) — the paper's stated future work
("train models across multiple pods").
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 512 if multi_pod else 256
    n = len(jax.devices())
    if n < need:
        raise RuntimeError(
            f"production mesh {shape} needs {need} devices but the jax "
            f"backend initialized with {n}; on CPU, set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=512 before any jax "
            f"use (a fresh process — the backend cannot be resized)")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh():
    """Whatever devices exist locally, as a 1-D data mesh (smoke tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",))


INPUT_SHAPES = {
    # name: (seq_len, global_batch, kind)
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}
