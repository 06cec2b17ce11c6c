"""Shared Pallas runtime helpers for the kernel subpackages."""
from __future__ import annotations

import jax


def default_interpret(backend: str | None = None) -> bool:
    """Pallas interpret-mode default: compiled on TPU, interpreter
    everywhere else (CPU CI, tests, dry-runs)."""
    return (backend or jax.default_backend()) != "tpu"


BACKENDS = ("jnp", "pallas", "auto")


def resolve_backend(backend: str) -> str:
    """Resolve a kernel-backend knob to a concrete backend.

    ``"jnp"`` and ``"pallas"`` are explicit.  ``"auto"`` picks the Pallas
    kernels where they compile natively (TPU, via
    :func:`default_interpret`) and the pure-jnp lowering everywhere else
    — interpret-mode Pallas is a validation tool, not a runtime path.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"known: {BACKENDS}")
    if backend == "auto":
        return "jnp" if default_interpret() else "pallas"
    return backend


def kernel_paths(cfg, mesh_devices: int = 1) -> dict:
    """The attention and SSD-mixer lowerings a run of ``cfg`` takes, as
    ``attn_apply``/``ssm_apply`` choose them: the resolved backend on one
    device, the jnp lowerings under a multi-device mesh (``pallas_call``
    has no partitioning rule)."""
    def path(backend):
        return "jnp" if mesh_devices > 1 else resolve_backend(backend)
    paths = {}
    if cfg.has_attention:
        paths["attention"] = path(cfg.attention_backend)
    if cfg.ssm is not None:
        paths["mixer"] = path(cfg.mixer_backend)
    return paths
