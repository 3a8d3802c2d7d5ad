"""Mesh-independent checkpointing with atomic commit and async save.

Format: a step directory ``step_<n>/`` holding one ``.npy`` per pytree leaf
plus ``manifest.json`` (treedef, shapes, dtypes, user metadata). Writes go to
``step_<n>.tmp`` and are committed by atomic rename — a crash mid-save never
corrupts the latest checkpoint (restart-safety). Restore rebuilds the pytree
and (optionally) re-shards every leaf onto a target mesh, so a job may
restart on a *different* device count (elastic scaling, DESIGN.md §8).

On a real multi-host cluster each host would write only its local shards;
this single-host implementation gathers leaves (``np.asarray``) and notes the
distinction here rather than hiding it.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import threading
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np


def _leaf_paths(tree) -> Dict[str, Any]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(p) for p in path) or "root"
        key = re.sub(r"[^A-Za-z0-9_.\-]", "_", key)
        out[key] = leaf
    return out


def save(directory: str, step: int, state, metadata: Optional[dict] = None,
         keep_last: int = 3) -> str:
    """Atomic checkpoint save; returns the committed path."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:09d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    leaves = _leaf_paths(state)
    manifest = {"step": step, "metadata": metadata or {}, "leaves": {}}
    for key, leaf in leaves.items():
        arr = np.asarray(jax.device_get(leaf))
        np.save(os.path.join(tmp, key + ".npy"), arr)
        manifest["leaves"][key] = {"shape": list(arr.shape),
                                   "dtype": str(arr.dtype)}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)          # atomic commit
    _gc(directory, keep_last)
    return final


def _gc(directory: str, keep_last: int):
    steps = sorted(_list_steps(directory))
    # keep_last=0 means "keep nothing": steps[:-0] is the EMPTY slice, which
    # silently kept everything — slice only when there is a tail to keep
    doomed = steps[:-keep_last] if keep_last > 0 else steps
    for s in doomed:
        shutil.rmtree(os.path.join(directory, f"step_{s:09d}"),
                      ignore_errors=True)


def _list_steps(directory: str):
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = re.fullmatch(r"step_(\d+)", name)
        if m and os.path.exists(os.path.join(directory, name, "manifest.json")):
            out.append(int(m.group(1)))
    return out


def latest_step(directory: str) -> Optional[int]:
    steps = _list_steps(directory)
    return max(steps) if steps else None


def read_manifest(directory: str, step: int) -> dict:
    """The committed manifest of one step (treedef keys, per-leaf
    shape/dtype, user metadata) — the structure-discovery entry point for
    consumers that must rebuild a ``like`` pytree from disk alone (the
    serving layer restoring frozen factors, ``repro.serve.model``)."""
    path = os.path.join(directory, f"step_{step:09d}", "manifest.json")
    with open(path) as f:
        return json.load(f)


class StructureMismatch(ValueError):
    """A checkpoint whose state structure differs from the restore target
    (missing leaves, another shape or dtype): not a corrupt file, so no
    older step of the same directory can be the right one either."""


def _validate_leaf(path: str, key: str, arr: np.ndarray, entry: dict,
                   like_leaf) -> None:
    """Fail fast, naming the offending leaf: (a) the on-disk array must match
    the manifest record (corruption / partial write), (b) the manifest record
    must match the restore target (structure drift — e.g. the rank changed
    between fit and serve, which previously surfaced only as an opaque jit
    shape error much later)."""
    m_shape = tuple(entry["shape"])
    if tuple(arr.shape) != m_shape or str(arr.dtype) != entry["dtype"]:
        raise ValueError(
            f"checkpoint {path}: leaf {key!r} on disk is "
            f"{tuple(arr.shape)}/{arr.dtype} but the manifest records "
            f"{m_shape}/{entry['dtype']} — corrupted or partially written")
    like_shape = tuple(np.shape(like_leaf))
    if like_shape != m_shape:
        raise StructureMismatch(
            f"checkpoint {path}: leaf {key!r} has shape {m_shape} but the "
            f"restore target expects {like_shape} — checkpoint/structure "
            f"drift (e.g. rank changed between fit and serve)")
    if hasattr(like_leaf, "dtype") and np.dtype(like_leaf.dtype) != arr.dtype:
        raise StructureMismatch(
            f"checkpoint {path}: leaf {key!r} has dtype {arr.dtype} but the "
            f"restore target expects {np.dtype(like_leaf.dtype)}")


def restore(directory: str, step: int, like,
            shard_fn: Optional[Callable[[str, np.ndarray], Any]] = None):
    """Restore into the structure of ``like``. ``shard_fn(key, arr)`` may
    device_put each leaf with a target sharding (elastic restore path);
    default is plain host arrays fed to jnp. Every loaded leaf is validated
    against the manifest's recorded shape/dtype AND the ``like`` structure —
    a drifted checkpoint fails here with the leaf named, not later inside
    jit."""
    path = os.path.join(directory, f"step_{step:09d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    leaves = _leaf_paths(like)
    recorded = manifest.get("leaves", {})
    missing = sorted(set(leaves) - set(recorded))
    if missing:
        raise StructureMismatch(
            f"checkpoint {path}: leaves {missing} absent from the manifest "
            f"(it records {sorted(recorded)}) — structure drift")
    out = {}
    for key, like_leaf in leaves.items():
        arr = np.load(os.path.join(path, key + ".npy"))
        _validate_leaf(path, key, arr, recorded[key], like_leaf)
        out[key] = shard_fn(key, arr) if shard_fn else arr
    flat, treedef = jax.tree_util.tree_flatten(like)
    paths = list(_leaf_paths(like).keys())
    restored = [out[k] for k in paths]
    return jax.tree_util.tree_unflatten(treedef, restored), manifest


class Checkpointer:
    """Async checkpointer: save() returns immediately, the write happens on a
    background thread (overlaps I/O with the next steps); wait() joins.

    A failed background write (disk full, bad leaf) is NOT silently
    swallowed: the worker exception is captured and re-raised — prefixed
    with the step it belongs to — at the next ``wait()`` or ``save_async()``
    call, so a caller cannot keep training against a checkpoint directory
    that is quietly serving a stale step."""

    def __init__(self, directory: str, keep_last: int = 3):
        self.directory = directory
        self.keep_last = keep_last
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._error_step: Optional[int] = None

    def save_async(self, step: int, state, metadata: Optional[dict] = None):
        self.wait()
        # snapshot to host before returning so the caller may mutate state
        host_state = jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                  state)
        # snapshot metadata too — callers pass live dicts (e.g. a growing
        # metric history) that must reflect THIS step in the manifest
        if metadata is not None:
            metadata = json.loads(json.dumps(metadata))

        def work():
            try:
                save(self.directory, step, host_state, metadata,
                     self.keep_last)
            except BaseException as e:   # re-raised on the caller's thread
                self._error = e
                self._error_step = step

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, step = self._error, self._error_step
            self._error = self._error_step = None
            raise RuntimeError(
                f"async checkpoint save of step {step} failed; the newest "
                f"on-disk checkpoint is stale") from err

    def latest(self) -> Optional[int]:
        return latest_step(self.directory)

    def restore_latest(self, like, shard_fn=None):
        step = self.latest()
        if step is None:
            return None
        state, manifest = restore(self.directory, step, like, shard_fn)
        return step, state, manifest
