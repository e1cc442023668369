"""Checkpoint store: atomic, sharded, elastic-restore friendly.

Layout per step::

    <dir>/step_000123.tmp-<nonce>/   (write everything, fsync)
        shard_00000.npz ... shard_NNNNN.npz
        manifest.json                (tree structure + leaf->shard map + meta)
    <dir>/step_000123/               (atomic rename when complete)

Properties that matter at 1000+ nodes:
  * LOGICAL (unsharded) layout: leaves are saved as full arrays, so restore
    works onto ANY mesh shape — this is what makes elastic re-mesh
    (runtime/elastic.py) a restore, not a resharding job;
  * atomic rename + manifest: a crashed writer never corrupts the latest
    checkpoint; readers only see directories with a manifest;
  * keep-k GC; auto-resume picks the newest complete step;
  * multi-host: every host writes only the shards it owns (here: one host
    owns all), and the manifest records the owner map.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
import shutil
import time
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ArtifactCorruption",
    "CheckpointManager",
    "save_checkpoint",
    "load_checkpoint",
    "load_arrays",
    "latest_step",
]

_MANIFEST = "manifest.json"


class ArtifactCorruption(ValueError):
    """A checkpoint shard's bytes do not match its manifest digest."""

    def __init__(self, shard: int, path, expected: str, actual: str):
        self.shard = shard
        self.path = str(path)
        self.expected = expected
        self.actual = actual
        super().__init__(
            f"checkpoint shard {shard} corrupt: {path} sha256 "
            f"{actual[:12]}… does not match manifest {expected[:12]}…")


def _sha256(path: pathlib.Path) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    items = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        items.append((key, leaf))
    return items, treedef


def save_checkpoint(
    directory: str | os.PathLike,
    step: int,
    tree: Any,
    *,
    shard_mb: int = 512,
    extra_meta: Optional[dict] = None,
) -> pathlib.Path:
    """Write one checkpoint atomically; returns the final path."""
    directory = pathlib.Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    final = directory / f"step_{step:08d}"
    tmp = directory / f"step_{step:08d}.tmp-{os.getpid()}-{int(time.time()*1e3)}"
    tmp.mkdir(parents=True)

    items, _ = _flatten_with_paths(tree)
    shard_bytes = shard_mb * 1024 * 1024
    shards: list[dict] = []
    cur: dict = {}
    cur_size = 0
    leaf_to_shard: dict = {}
    for key, leaf in items:
        arr = np.asarray(jax.device_get(leaf))
        if cur_size + arr.nbytes > shard_bytes and cur:
            shards.append(cur)
            cur, cur_size = {}, 0
        cur[key] = arr
        cur_size += arr.nbytes
        leaf_to_shard[key] = len(shards)
    if cur:
        shards.append(cur)

    for i, shard in enumerate(shards):
        # npz keys cannot contain '/': encode
        enc = {k.replace("/", "::"): v for k, v in shard.items()}
        path = tmp / f"shard_{i:05d}.npz"
        with open(path, "wb") as f:
            np.savez(f, **enc)
            f.flush()
            os.fsync(f.fileno())

    manifest = {
        "step": step,
        "format": 1,
        "n_shards": len(shards),
        "leaf_to_shard": leaf_to_shard,
        # per-shard content digests: load_arrays verifies these before
        # deserializing, so silent on-disk corruption fails loudly with
        # the shard named instead of NaN-ing the first forward pass
        "shard_digests": [
            _sha256(tmp / f"shard_{i:05d}.npz") for i in range(len(shards))
        ],
        # npz stores dtypes numpy lacks (bfloat16) as raw void bytes; the
        # loader views them back through these names
        "leaf_dtypes": {
            k: str(v.dtype) for shard in shards for k, v in shard.items()
            if v.dtype.kind == "V"
        },
        "time": time.time(),
        "meta": extra_meta or {},
    }
    mpath = tmp / _MANIFEST
    with open(mpath, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())

    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)  # atomic publish
    return final


def latest_step(directory: str | os.PathLike) -> Optional[int]:
    directory = pathlib.Path(directory)
    if not directory.exists():
        return None
    steps = []
    for p in directory.iterdir():
        if p.is_dir() and p.name.startswith("step_") and ".tmp" not in p.name:
            if (p / _MANIFEST).exists():  # complete checkpoints only
                steps.append(int(p.name.split("_")[1]))
    return max(steps) if steps else None


def load_arrays(
    directory: str | os.PathLike,
    *,
    step: Optional[int] = None,
    placer: Optional[Any] = None,
    verify: bool = True,
    _corrupt_shards=(),
) -> tuple[dict[str, Any], int, dict]:
    """Load a checkpoint as a flat ``path -> array`` dict, no ``like`` tree.

    This is the structure-free restore used by consumers that rebuild
    their objects from manifest metadata (e.g. serve/artifacts.py, where
    the tree holds QuantizedLinear fields that are not plain pytrees).

    ``placer``: optional ``f(key, np_array) -> array`` applied to each
    leaf as it streams out of its npz shard — the distributed loader
    commits every leaf straight to its device sharding here, so a large
    artifact never exists as one unsharded host+device copy.

    ``verify``: check each shard's SHA-256 against the manifest and
    raise :class:`ArtifactCorruption` on mismatch.  Manifests written
    before digests existed load with a warning.  ``_corrupt_shards`` is
    the fault-injection hook: listed shard indices are treated as if
    their bytes had rotted (see serve/faults.py).
    Returns (arrays, step, meta).
    """
    directory = pathlib.Path(directory)
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
    path = directory / f"step_{step:08d}"
    manifest = json.loads((path / _MANIFEST).read_text())
    digests = manifest.get("shard_digests")
    if verify and digests is None:
        warnings.warn(
            f"{path} manifest predates shard checksums; loading unverified",
            stacklevel=2)
    void_dtypes = manifest.get("leaf_dtypes", {})
    arrays: dict[str, Any] = {}
    for i in range(manifest["n_shards"]):
        spath = path / f"shard_{i:05d}.npz"
        if verify and digests is not None:
            actual = _sha256(spath)
            if i in _corrupt_shards:
                actual = "0" * 64
            if actual != digests[i]:
                raise ArtifactCorruption(i, spath, digests[i], actual)
        with np.load(spath) as z:
            for k in z.files:
                key = k.replace("::", "/")
                arr = z[k]
                if key in void_dtypes:
                    arr = arr.view(jnp.dtype(void_dtypes[key]))
                arrays[key] = arr if placer is None else placer(key, arr)
    return arrays, step, manifest.get("meta", {})


def load_checkpoint(
    directory: str | os.PathLike,
    like: Any,
    *,
    step: Optional[int] = None,
    shardings: Any = None,
) -> tuple[Any, int, dict]:
    """Restore a pytree (structure given by ``like``).

    ``shardings``: optional same-structure tree of NamedShardings — leaves
    are placed directly onto the (possibly different) current mesh, which
    is the elastic-restore path.
    Returns (tree, step, meta).
    """
    arrays, step, _meta = load_arrays(directory, step=step)
    items, treedef = _flatten_with_paths(like)
    leaves = []
    sh_items = None
    if shardings is not None:
        sh_items, _ = _flatten_with_paths(shardings)
    for idx, (key, leaf) in enumerate(items):
        if key not in arrays:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = arrays[key].astype(leaf.dtype) if hasattr(leaf, "dtype") else arrays[key]
        if sh_items is not None:
            leaves.append(jax.device_put(arr, sh_items[idx][1]))
        else:
            leaves.append(jax.numpy.asarray(arr))
    return treedef.unflatten(leaves), step, _meta


@dataclasses.dataclass
class CheckpointManager:
    """keep-k policy + convenience wrapper used by the train driver."""

    directory: str
    keep: int = 3
    save_every: int = 50

    def maybe_save(self, step: int, tree: Any, **meta) -> Optional[pathlib.Path]:
        if step % self.save_every:
            return None
        p = save_checkpoint(self.directory, step, tree, extra_meta=meta)
        self.gc()
        return p

    def gc(self):
        d = pathlib.Path(self.directory)
        if not d.exists():
            return
        steps = sorted(
            int(p.name.split("_")[1])
            for p in d.iterdir()
            if p.is_dir() and p.name.startswith("step_") and ".tmp" not in p.name
            and (p / _MANIFEST).exists()
        )
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(d / f"step_{s:08d}", ignore_errors=True)
        # clean stale tmp dirs from crashed writers
        for p in d.iterdir():
            if ".tmp-" in p.name:
                shutil.rmtree(p, ignore_errors=True)

    def restore_latest(self, like: Any, shardings=None):
        return load_checkpoint(self.directory, like, shardings=shardings)
