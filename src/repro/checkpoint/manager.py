"""Checkpoint manager on top of the plain file API.

The paper's technique, applied to training state: ``save()`` returns once
the checkpoint bytes are *synchronously durable* in the fast tier (when the
FS is NVCache-backed, that is the NVMM log append — Alg. 1), while the
cleanup thread drains to the blob tier in the background, overlapping the
next training steps.  The manifest write is the commit point (the paper's
group-commit at application granularity): a crash mid-save restores the
previous step, never a torn pytree.

Restore supports *resharding*: ``restore(slice_rows=...)`` reads only the
row-chunks a host needs, which is how elastic scaling re-slices state to a
new device count.
"""
from __future__ import annotations

import hashlib
import json
from typing import Callable, Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint import codec


def _flatten(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((key, leaf))
    return out, treedef


def tree_digest(tree) -> str:
    """sha256 over every leaf's key, dtype, shape and bytes: two trees with
    equal digests are bitwise equal."""
    h = hashlib.sha256()
    for key, leaf in _flatten(tree)[0]:
        a = np.ascontiguousarray(leaf)
        h.update(f"{key}:{a.dtype.str}:{a.shape};".encode())
        h.update(a.reshape(-1).view(np.uint8))
    return h.hexdigest()


class CheckpointManager:
    def __init__(self, fs, directory: str = "/ckpt", *, keep: int = 2,
                 encoding: int = codec.ENC_ZSTD):
        self.fs = fs
        self.dir = directory.rstrip("/")
        self.keep = keep
        self.encoding = encoding
        self._manifest_path = f"{self.dir}/MANIFEST.json"
        self._manifest_fd = None      # held open: close() would wait behind
        self._deferred_fds: list = []  # the whole FIFO log drain

    # ------------------------------------------------------------------ save
    def save(self, step: int, tree) -> dict:
        with obs.span("ckpt.save_us", step=step) as sp:
            # finalize previous steps' files now (their background drain has
            # had a full checkpoint interval to complete — close() barely
            # blocks)
            self.finalize()
            path = f"{self.dir}/step_{step:08d}.ckpt"
            w = codec.Writer(self.fs, path, encoding=self.encoding,
                             close_on_finish=False)
            flat, _ = _flatten(tree)
            sp.set(bytes=sum(getattr(leaf, "nbytes", 0) for _, leaf in flat),
                   encode_workers=w.workers)
            for key, leaf in flat:
                w.put_leaf(key, leaf)
            info = w.finish()
            self._deferred_fds.append(w.fd)
            manifest = self._read_manifest()
            manifest["steps"] = sorted(set(manifest.get("steps", []) + [step]))
            manifest["latest"] = max(manifest["steps"])
            manifest["files"] = {**manifest.get("files", {}),
                                 str(step): {"path": path, **info}}
            self._gc(manifest)
            # the manifest write commits the checkpoint (crash before it ->
            # previous step restores; the data file is garbage-collected)
            self._write_manifest(manifest)
        return {"step": step, **info}

    def finalize(self) -> None:
        """Close deferred checkpoint files (waits for their drain).  A close
        error (e.g. a drain barrier that timed out) propagates; the files
        not yet closed stay deferred."""
        with obs.span("ckpt.finalize_us"):
            while self._deferred_fds:
                self.fs.close(self._deferred_fds.pop(0))

    def close(self) -> None:
        self.finalize()
        if self._manifest_fd is not None:
            fd, self._manifest_fd = self._manifest_fd, None
            self.fs.close(fd)

    # --------------------------------------------------------------- restore
    def latest_step(self) -> Optional[int]:
        m = self._read_manifest()
        return m.get("latest")

    def restore(self, tree_like, step: Optional[int] = None,
                slice_rows: Optional[Callable[[str, tuple], Optional[tuple]]] = None):
        """Rebuild a pytree shaped like ``tree_like``.

        ``slice_rows(key, global_shape) -> (lo, hi) | None`` selects a
        row-range per leaf for resharded restore."""
        m = self._read_manifest()
        step = step if step is not None else m.get("latest")
        if step is None:
            raise FileNotFoundError("no checkpoint")
        path = m["files"][str(step)]["path"]
        with obs.span("ckpt.restore_us", step=step) as sp:
            r = codec.Reader(self.fs, path)
            flat, treedef = _flatten(tree_like)
            leaves = []
            for key, like in flat:
                rows = slice_rows(key, tuple(np.shape(like))) if slice_rows else None
                arr = r.read_leaf(key, rows=rows)
                leaves.append(arr)
            r.close()
            sp.set(bytes=sum(a.nbytes for a in leaves))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # ------------------------------------------------------------- internals
    def _mfd(self):
        if self._manifest_fd is None:
            self._manifest_fd = self.fs.open(self._manifest_path)
        return self._manifest_fd

    def _read_manifest(self) -> dict:
        """{} only for a missing or empty manifest; a corrupt manifest or a
        failed read raises rather than silently restarting from step 0."""
        fd = self._mfd()
        size = self.fs.size(fd)
        return json.loads(self.fs.pread(fd, size, 0)) if size else {}

    def _write_manifest(self, manifest: dict) -> None:
        with obs.span("ckpt.manifest_us"):
            blob = json.dumps(manifest).encode()
            fd = self._mfd()
            # single pwrite -> one atomic committed group in NVCache
            self.fs.pwrite(fd, blob.ljust(max(self.fs.size(fd), len(blob)), b" "), 0)
            self.fs.fsync(fd)

    def _gc(self, manifest: dict) -> None:
        steps = manifest.get("steps", [])
        while len(steps) > self.keep:
            steps.pop(0)
        manifest["steps"] = steps
        manifest["files"] = {k: v for k, v in manifest.get("files", {}).items()
                             if int(k) in steps}
