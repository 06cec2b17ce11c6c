"""Process-mesh context for data-parallel training.

``init_distributed(world_size, rank, coordinator)`` brings up
``jax.distributed`` (gloo collectives on CPU — the container has no
NCCL) and returns a :class:`DistContext` over a 1-D ``data`` mesh of
every device in the job.  ``world_size=1`` degenerates to a local
single-device mesh with no distributed runtime, so the same trainer
code path serves both cases (and the world=1 oracle test runs
in-process).

The synchronization model is GSPMD, not hand-written ``psum``: the
train step is jitted with batch inputs sharded ``P("data")`` and state
in/out replicated ``P()`` — XLA inserts the gradient all-reduce (and
overlaps it with backward compute where the schedule allows), which is
exactly the FireCaffe reduction this package's bench meters.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def init_distributed(world_size: int = 1, rank: int = 0,
                     coordinator: Optional[str] = None) -> "DistContext":
    """Initialize the distributed runtime (when ``world_size > 1``) and
    build the process-mesh context.  Must run before any other jax call
    in the process — ``jax.distributed.initialize`` cannot attach to an
    already-initialized backend."""
    world_size = int(world_size)
    if world_size > 1:
        if coordinator is None:
            raise ValueError("world_size > 1 requires coordinator "
                             "('host:port' of rank 0)")
        if not 0 <= int(rank) < world_size:
            raise ValueError(f"rank {rank} outside world of {world_size}")
        # CPU collectives need an explicit cross-process backend.  Must
        # not query the backend here (jax.default_backend() would
        # initialize it, which forbids distributed init) — the setting
        # is inert on GPU/TPU.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # every parameter is given: no cluster environment is probed
        # (the TPU probes ask a metadata server, which a host may lack)
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=world_size,
                                   process_id=int(rank),
                                   cluster_detection_method="deactivate")
        devices = np.array(jax.devices())
    else:
        devices = np.array(jax.devices()[:1])
    mesh = Mesh(devices, (DATA_AXIS,))
    return DistContext(world_size=world_size, rank=int(rank),
                       coordinator=coordinator, mesh=mesh)


@dataclasses.dataclass
class DistContext:
    """One rank's view of the data-parallel job."""

    world_size: int
    rank: int
    coordinator: Optional[str]
    mesh: Mesh

    @property
    def is_coordinator(self) -> bool:
        return self.rank == 0

    @property
    def devices(self) -> int:
        return self.mesh.devices.size

    # ------------------------------------------------------- shardings
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(DATA_AXIS))

    def row_range(self, global_rows: int) -> tuple:
        """The contiguous ``[lo, hi)`` slice of the global batch this
        process's devices own (``jax.devices()`` orders process-major,
        so shards are contiguous per process)."""
        if global_rows % self.devices:
            raise ValueError(f"global batch {global_rows} not divisible "
                             f"by {self.devices} devices")
        local = jax.local_device_count() if self.world_size > 1 else 1
        per_dev = global_rows // self.devices
        lo = self.rank * local * per_dev
        return lo, lo + local * per_dev

    # ----------------------------------------------------- global arrays
    def global_batch(self, local_tree: Any, global_rows: int) -> Any:
        """Per-rank host shards -> one global jax.Array tree sharded
        ``P("data")`` on dim 0."""
        sh = self.batch_sharding()

        def lift(x):
            x = np.asarray(x)
            return jax.make_array_from_process_local_data(
                sh, x, (global_rows,) + x.shape[1:])
        return jax.tree.map(lift, local_tree)

    def replicate(self, tree: Any) -> Any:
        """Host (or local-device) tree -> fully replicated global arrays
        (every rank must pass identical values)."""
        sh = self.replicated()
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                sh, np.asarray(x)), tree)

    # ------------------------------------------------------------- jit
    def jit_step(self, step_fn, *, donate_state: bool = True):
        """Wrap a bare ``(state, batch) -> (state, metrics)`` step (from
        ``make_train_step(jit_compile=False)``) in the data-parallel
        jit: batch sharded over ``data``, state/metrics replicated, the
        input state donated exactly as the single-process path does."""
        repl, bsh = self.replicated(), self.batch_sharding()
        return jax.jit(step_fn, in_shardings=(repl, bsh),
                       out_shardings=(repl, repl),
                       donate_argnums=(0,) if donate_state else ())

    # ------------------------------------------------------- agreement
    def allgather(self, value) -> np.ndarray:
        """Gather a small per-rank value to every rank (shape
        ``(world, ...)``).  Identity-stack at world=1."""
        arr = np.asarray(value)
        if self.world_size <= 1:
            return arr[None]
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(arr))

    def agree(self, value, what: str = "value"):
        """Assert all ranks hold the same scalar/array; returns it.
        Catches divergent resume (one rank restored a different
        checkpoint step) before it poisons a collective."""
        gathered = self.allgather(value)
        if not all(np.array_equal(gathered[0], g) for g in gathered[1:]):
            raise RuntimeError(
                f"ranks disagree on {what}: "
                f"{[np.asarray(g).tolist() for g in gathered]}")
        return value
