"""Checkpointing: atomic, async-capable, elastic, self-verifying.

Layout:  <dir>/step_<N>/arrays.npz  + manifest.json
  * arrays are stored with LOGICAL (unsharded) shapes keyed by pytree path,
    so restore onto a different mesh / device count just re-applies the
    sharding rules — that is the elastic-rescale path (lose a pod, restore
    onto the survivors);
  * writes go to step_<N>.tmp then rename (atomic on POSIX);
  * ``save_async`` runs the host-side write in a thread so the training
    loop only blocks for the device->host copy;
  * every save records a SHA-256 of ``arrays.npz`` in its manifest
    (``arrays_sha256``); loads verify it, so a truncated or bit-rotted
    checkpoint surfaces as a typed :class:`CheckpointCorruptError` naming
    the offending path — never a raw pickle/zip/numpy error — and
    :func:`latest_valid_step` finds the newest checkpoint that still
    verifies (the supervised-streaming rollback hook, DESIGN.md §14).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
import time
import zipfile
from typing import Any

import jax
import numpy as np

SEP = "/"


class CheckpointCorruptError(RuntimeError):
    """A checkpoint on disk is truncated, garbled, or fails its checksum.

    Always names the offending file; raised instead of whatever raw
    ``zipfile``/``pickle``/``numpy`` error the damage would otherwise
    surface as, so callers can catch ONE type to trigger rollback."""

    def __init__(self, path: str, why: str):
        self.path = path
        self.why = why
        super().__init__(f"corrupt checkpoint at {path}: {why}")


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _flatten(tree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = []
        for p in path:
            if hasattr(p, "key"):
                keys.append(str(p.key))
            elif hasattr(p, "name"):
                keys.append(str(p.name))
            elif hasattr(p, "idx"):
                keys.append(str(p.idx))
        flat[SEP.join(keys)] = np.asarray(leaf)
    return flat


def _tree_def(tree):
    return jax.tree_util.tree_structure(tree)


def save(directory: str, step: int, state: Any, extra: dict | None = None,
         chunk: int | None = None) -> str:
    """Blocking save. `state` is any pytree of arrays.

    Its three phases are profiler spans: ``repro.ckpt.wait`` (the device
    finishing ``state``), ``repro.ckpt.fetch`` (the device-to-host copy;
    stats ``leaves`` and ``bytes``) and ``repro.ckpt.write`` (npz,
    checksum, manifest, rename; stat ``bytes``, the npz on disk).  Each
    carries ``chunk``, the stream chunk the state follows, when given."""
    ids = {} if chunk is None else {"chunk": chunk}
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    with jax.profiler.TraceAnnotation("repro.ckpt.wait", **ids):
        jax.block_until_ready(state)
    with jax.profiler.TraceAnnotation("repro.ckpt.fetch", **ids) as span:
        flat = _flatten(state)
        total_bytes = int(sum(a.nbytes for a in flat.values()))
        span.set_metadata(leaves=len(flat), bytes=total_bytes)
    with jax.profiler.TraceAnnotation("repro.ckpt.write", **ids) as span:
        arrays = os.path.join(tmp, "arrays.npz")
        np.savez(arrays, **flat)
        span.set_metadata(bytes=os.path.getsize(arrays))
        manifest = {
            "step": step,
            "time": time.time(),
            "num_arrays": len(flat),
            "total_bytes": total_bytes,
            "arrays_sha256": _sha256_file(arrays),
            "extra": extra or {},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    return final


class AsyncCheckpointer:
    """Device->host copy on the caller thread; disk write in background."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def save(self, step: int, state: Any, extra: dict | None = None) -> None:
        self.wait()
        host_state = jax.tree.map(np.asarray, state)  # sync copy out

        def work():
            try:
                save(self.directory, step, host_state, extra)
                self._gc()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self) -> None:
        steps = sorted(list_steps(self.directory))
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)


def list_steps(directory: str) -> list[int]:
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        if name.startswith("step_") and not name.endswith(".tmp"):
            try:
                out.append(int(name[5:]))
            except ValueError:
                pass
    return sorted(out)


def latest_step(directory: str) -> int | None:
    steps = list_steps(directory)
    return steps[-1] if steps else None


def load_arrays(directory: str, step: int, verify: bool = True
                ) -> dict[str, np.ndarray]:
    """Read a step's arrays as a ``{pytree path: ndarray}`` dict, fully
    materialized, raising :class:`CheckpointCorruptError` on truncated or
    garbled files.  ``verify=True`` (default) additionally checks the
    manifest's ``arrays_sha256`` when present (checkpoints written before
    checksumming landed verify structurally only)."""
    path = os.path.join(directory, f"step_{step:08d}", "arrays.npz")
    if verify:
        sha = read_manifest(directory, step).get("arrays_sha256")
        if sha is not None:
            try:
                actual = _sha256_file(path)
            except OSError as e:
                raise CheckpointCorruptError(path, f"unreadable: {e}") \
                    from e
            if actual != sha:
                raise CheckpointCorruptError(
                    path, f"SHA-256 mismatch: manifest says {sha[:12]}…, "
                          f"file hashes to {actual[:12]}… (truncated write "
                          "or on-disk corruption)")
    try:
        with np.load(path, allow_pickle=False) as data:
            return {k: np.asarray(data[k]) for k in data.files}
    except CheckpointCorruptError:
        raise
    except Exception as e:
        # zipfile.BadZipFile, EOFError, OSError, ValueError from a garbage
        # member, KeyError from a torn index — one typed error, named path
        raise CheckpointCorruptError(
            path, f"{type(e).__name__}: {e}") from e


def verify_step(directory: str, step: int) -> None:
    """Raise :class:`CheckpointCorruptError` unless step ``step`` is fully
    readable (manifest parses, arrays decompress, checksum matches)."""
    load_arrays(directory, step, verify=True)


def latest_valid_step(directory: str) -> tuple[int | None, list[int]]:
    """Newest step that verifies, plus the (newer) corrupt steps skipped
    on the way — the rollback primitive: ``(None, [...])`` means no
    checkpoint survived at all."""
    corrupt: list[int] = []
    for step in reversed(list_steps(directory)):
        try:
            verify_step(directory, step)
        except CheckpointCorruptError:
            corrupt.append(step)
        else:
            return step, corrupt
    return None, corrupt


def restore(directory: str, step: int, like: Any, shardings: Any | None = None
            ) -> Any:
    """Restore into the structure of `like` (a pytree of arrays or
    ShapeDtypeStructs).  If `shardings` is given (pytree of NamedSharding),
    arrays are device_put with them — restoring onto a different mesh than
    the one that saved is supported because stored shapes are logical."""
    data = load_arrays(directory, step)
    flat_like = _flatten(like)
    missing = set(flat_like) - set(data)
    if missing:
        raise KeyError(f"checkpoint missing arrays: {sorted(missing)[:5]}...")
    leaves_like, treedef = jax.tree_util.tree_flatten(like)
    keys = list(_flatten(like).keys())
    arrays = [data[k] for k in keys]
    restored = jax.tree_util.tree_unflatten(treedef, arrays)
    if shardings is not None:
        restored = jax.tree.map(
            lambda a, s: jax.device_put(a, s), restored, shardings)
    else:
        restored = jax.tree.map(jax.numpy.asarray, restored)
    return restored


def read_manifest(directory: str, step: int) -> dict:
    path = os.path.join(directory, f"step_{step:08d}", "manifest.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        raise CheckpointCorruptError(
            path, f"{type(e).__name__}: {e}") from e
