"""Checkpointing: parameter/optimizer pytrees -> sharded .npz files with a
JSON manifest, plus S3 export (the paper copies all trained models to S3
after training).  Leaves are flattened by path; files are split so no
single shard exceeds ``shard_bytes``.

A checkpoint directory is *valid* iff ``manifest.json`` parses and every
shard it references loads with every declared key.  Anything else — a
missing or truncated manifest, a torn final shard from a preemption
mid-write — raises :class:`CheckpointError` so callers (in particular
:class:`repro.checkpoint.CheckpointManager`) can fall back to an older
checkpoint instead of crashing with a bare ``KeyError``/``BadZipFile``.
"""
from __future__ import annotations

import json
import os
import zipfile
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.core.artifacts import S3Store

MANIFEST = "manifest.json"


class CheckpointError(RuntimeError):
    """A checkpoint directory is unreadable (missing/truncated manifest,
    torn shard).  Distinct from shape/key mismatches against ``like=``,
    which stay ``ValueError``/``KeyError`` — those mean the checkpoint is
    intact but *wrong* for the requested restore."""


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path)
        flat[key] = np.asarray(leaf)
    return flat


def save_checkpoint(directory: str, tree, step: int = 0,
                    shard_bytes: int = 1 << 30,
                    metadata: Optional[dict] = None,
                    fsync: bool = False) -> str:
    """Write ``tree`` into ``directory``.  Shards first, manifest last, so
    a torn write is detectable (manifest missing => invalid).  With
    ``fsync=True`` the manifest (and its directory entry) are fsynced —
    used by the atomic manager path before the rename that publishes the
    checkpoint."""
    d = Path(directory)
    d.mkdir(parents=True, exist_ok=True)
    flat = _flatten(tree)
    shards, cur, cur_bytes = [], {}, 0
    for k in sorted(flat):
        arr = flat[k]
        if cur and cur_bytes + arr.nbytes > shard_bytes:
            shards.append(cur)
            cur, cur_bytes = {}, 0
        cur[k] = arr
        cur_bytes += arr.nbytes
    if cur:
        shards.append(cur)

    manifest = {"step": step, "n_shards": len(shards),
                "keys": {}, "metadata": metadata or {}}
    for i, shard in enumerate(shards):
        fname = f"shard_{i:04d}.npz"
        np.savez(d / fname, **{k.replace("/", "|"): v
                               for k, v in shard.items()})
        if fsync:                       # shards durable *before* manifest
            fd = os.open(d / fname, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        for k, v in shard.items():
            manifest["keys"][k] = {"shard": fname, "shape": list(v.shape),
                                   "dtype": str(v.dtype)}
    mpath = d / MANIFEST
    with open(mpath, "w") as f:
        f.write(json.dumps(manifest, indent=1))
        if fsync:
            f.flush()
            os.fsync(f.fileno())
    if fsync:
        dirfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dirfd)
        finally:
            os.close(dirfd)
    return str(d)


def read_manifest(directory: str) -> dict:
    """Parse ``manifest.json`` or raise :class:`CheckpointError` with an
    actionable message (missing vs truncated/corrupt)."""
    mpath = Path(directory) / MANIFEST
    if not mpath.exists():
        raise CheckpointError(
            f"no {MANIFEST} in {directory} — checkpoint incomplete "
            f"(torn write or wrong directory)")
    try:
        manifest = json.loads(mpath.read_text())
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CheckpointError(
            f"{mpath} is truncated or corrupt: {e}") from e
    if not isinstance(manifest, dict) or "keys" not in manifest:
        raise CheckpointError(f"{mpath} has no 'keys' table — not a "
                              f"checkpoint manifest")
    return manifest


def _as_declared(arr: np.ndarray, dtype: str) -> np.ndarray:
    """``np.savez`` stores dtypes numpy does not define (bfloat16 and
    the other ml_dtypes) as raw ``|V<n>`` bytes; view them back as the
    dtype the manifest declares.  A view, not a cast: no cast exists from
    raw bytes, and the bits are the checkpoint's."""
    want = np.dtype(jax.numpy.dtype(dtype))
    if arr.dtype.kind == "V" and arr.dtype.itemsize == want.itemsize:
        return arr.view(want)
    return arr


def load_checkpoint(directory: str, like=None):
    """Returns (tree_or_flat_dict, step).  With ``like`` provided, leaves
    are restored into that pytree structure (shape-checked; dtype-only
    mismatches are cast to the ``like`` leaf's dtype, so e.g. a float32
    checkpoint restores into a bf16 state and vice versa).  A leaf of
    ``like`` that is a ``jax.ShapeDtypeStruct`` restores to a host array,
    which lets a caller free its device copy before placing the restored
    one; any other leaf restores to a device array."""
    d = Path(directory)
    manifest = read_manifest(d)
    flat: Dict[str, np.ndarray] = {}
    by_shard: Dict[str, list] = {}
    for k, info in manifest["keys"].items():
        by_shard.setdefault(info["shard"], []).append(k)
    for fname, keys in by_shard.items():
        try:
            with np.load(d / fname) as z:
                for k in keys:
                    flat[k] = _as_declared(z[k.replace("/", "|")],
                                           manifest["keys"][k]["dtype"])
        except (FileNotFoundError, zipfile.BadZipFile, OSError, EOFError,
                KeyError, ValueError) as e:
            raise CheckpointError(
                f"shard {fname} in {directory} is missing or torn "
                f"({type(e).__name__}: {e}); manifest declares "
                f"{len(keys)} keys in it") from e
    if like is None:
        return flat, manifest["step"]

    leaves_like, treedef = jax.tree_util.tree_flatten_with_path(like)
    new_leaves = []
    for path, leaf in leaves_like:
        key = "/".join(
            str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
            for k in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing {key}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch {key}: "
                             f"{arr.shape} vs {leaf.shape}")
        want = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
        if arr.dtype != want:          # dtype-only mismatch: cast, don't crash
            arr = arr.astype(want)
        new_leaves.append(arr if isinstance(leaf, jax.ShapeDtypeStruct)
                          else jax.numpy.asarray(arr, dtype=want))
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(like), new_leaves)
    return tree, manifest["step"]


def export_to_s3(directory: str, s3: S3Store, prefix: str) -> int:
    """Paper: 'all models are copied to S3 cloud storage following
    training'.  Recurses so the manager's ``step_*/`` layout exports with
    its structure intact; hidden entries (``.tmp-*`` in-flight writes,
    ``.old-*`` aside copies) are never uploaded.  Returns number of
    objects uploaded."""
    root = Path(directory)
    n = 0
    for f in sorted(root.rglob("*")):
        rel = f.relative_to(root)
        if f.is_file() and not any(part.startswith(".")
                                   for part in rel.parts):
            s3.put_file(f"{prefix}/{rel.as_posix()}", f)
            n += 1
    return n
