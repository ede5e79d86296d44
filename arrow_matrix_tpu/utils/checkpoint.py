"""Iteration-state checkpoint/resume for long iterated-SpMM runs.

The reference's only resume point is the decomposition artifact on disk
(offline/online split, reference arrow/common/graphio.py:131-191); a
crashed 50-iteration run restarts from iteration 0.  Here the *runtime*
state — the feature array X and the iteration counter — checkpoints
too, through orbax when available (it writes sharded ``jax.Array``s
per-shard without gathering to host, the TPU-native answer for
multi-host meshes) with a plain ``.npz`` fallback otherwise.

State layout note: X is saved exactly as carried (level-0 row order,
flat or feature-major depending on the execution mode); the executor
that resumes must be built identically — the checkpoint records the
shape and a layout tag to fail loudly on mismatch instead of silently
permuting rows.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
from typing import Optional

import jax
import numpy as np

# Checkpoint format version: bump when the saved state's meaning
# changes (not when orbax/npz encodings differ).  Version 1 adds the
# version + layout tags themselves; untagged checkpoints (version 0,
# pre-graft-heal) still load but cannot be layout-verified.
CHECKPOINT_VERSION = 1


class CheckpointIntegrityError(RuntimeError):
    """The checkpoint's bytes do not match its sha256 sidecar: the
    state on disk was corrupted after it was written (bit rot, a torn
    concurrent writer, an injected ``corrupt`` fault).  Loading it
    would silently poison every subsequent iteration; callers either
    fail loudly (batch CLIs) or discard the checkpoint and recompute
    (graft-serve)."""


def _orbax():
    try:
        import orbax.checkpoint as ocp

        return ocp
    except ImportError:
        return None


def _meta_path(path: str) -> str:
    return path + ".meta.json"


def _write_meta(path: str, step: int, layout: Optional[str]) -> None:
    meta = {"version": CHECKPOINT_VERSION, "step": int(step),
            "layout": layout}
    tmp = _meta_path(path) + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(meta, fh)
    os.replace(tmp, _meta_path(path))


def _read_meta(path: str) -> Optional[dict]:
    try:
        with open(_meta_path(path), encoding="utf-8") as fh:
            return json.load(fh)
    except FileNotFoundError:
        return None
    except (ValueError, OSError) as e:
        # A malformed/unreadable sidecar degrades the checkpoint to
        # legacy (unverifiable) status with a loud warning — it must
        # never turn a loadable state into a crash.
        print(f"[checkpoint] WARNING: metadata at {_meta_path(path)} "
              f"is unreadable ({type(e).__name__}: {e}); treating the "
              f"checkpoint as legacy/untagged", file=sys.stderr)
        return None


#: Suffixes of a save in flight (save_state's staging/previous
#: directories and the npz temp file): never a checkpoint stem.
_TRANSIENT = (".saving", ".prev", ".tmp.npz")


def list_checkpoints(ckpt_dir: str, prefix: str = "ck_") -> list:
    """Stems of every checkpoint under ``ckpt_dir`` with ``prefix``,
    across both backends (orbax directories and ``.npz`` files),
    sorted.  A stem is what ``load_state``/``save_state`` take as
    ``path`` — graft-reshard's checkpoint migration enumerates these."""
    stems = set()
    try:
        entries = os.listdir(ckpt_dir)
    except OSError:
        return []
    for e in entries:
        p = os.path.join(ckpt_dir, e)
        if not e.startswith(prefix) or e.endswith(_TRANSIENT):
            continue
        if e.endswith(".npz"):
            stems.add(p[: -len(".npz")])
        elif os.path.isdir(p):
            stems.add(p)
    return sorted(stems)


def checkpoint_layout_tag(path: str) -> Optional[str]:
    """The layout tag the checkpoint at ``path`` (a stem) was saved
    with, without loading the state; None for untagged/legacy."""
    path = os.path.abspath(path)
    meta = _read_meta(path)
    if meta is not None:
        return meta.get("layout") or None
    npz = path + ".npz"
    if os.path.exists(npz):
        try:
            with np.load(npz) as z:
                if "layout" in z.files:
                    return str(z["layout"]) or None
        except (OSError, ValueError):
            return None
    return None


def _sha_path(npz_path: str) -> str:
    return npz_path + ".sha256"


def _file_sha256(p: str) -> str:
    h = hashlib.sha256()
    with open(p, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _write_sha(npz_path: str) -> None:
    tmp = _sha_path(npz_path) + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        fh.write(_file_sha256(npz_path) + "\n")
    os.replace(tmp, _sha_path(npz_path))


def _verify_sha(npz_path: str) -> None:
    """Raise :class:`CheckpointIntegrityError` when the npz bytes do
    not match the sha256 sidecar; a missing/unreadable sidecar skips
    the check (pre-sidecar checkpoints keep loading)."""
    try:
        with open(_sha_path(npz_path), encoding="utf-8") as fh:
            want = fh.read().strip()
    except (FileNotFoundError, OSError):
        return
    if not want:
        return
    got = _file_sha256(npz_path)
    if got != want:
        raise CheckpointIntegrityError(
            f"checkpoint {npz_path} fails sha256 verification "
            f"(sidecar records {want[:12]}..., file hashes "
            f"{got[:12]}...) — the state on disk was corrupted after "
            f"it was written; delete it (and its .sha256 sidecar) to "
            f"recompute from scratch")


def checkpoint_meta(path: str) -> Optional[dict]:
    """Best-effort ``{"version", "step", "layout"}`` of the checkpoint
    at ``path`` without loading the state, or None when absent or
    unreadable.  Pre-version (legacy) npz checkpoints report
    ``version: 0`` — callers warn loudly and skip layout verification
    instead of crashing (the graft-serve resume contract)."""
    path = os.path.abspath(path)
    try:
        if os.path.isdir(path):
            return _read_meta(path)
        if os.path.exists(path + ".npz"):
            with np.load(path + ".npz") as z:
                if "version" not in z.files:
                    return {"version": 0, "step": int(z["step"]),
                            "layout": None}
                layout = (str(z["layout"]) if "layout" in z.files
                          else "")
                return {"version": int(z["version"]),
                        "step": int(z["step"]),
                        "layout": layout or None}
    except Exception as e:  # noqa: BLE001 — metadata probing must not
        # crash the resume path; the load itself still verifies.
        print(f"[checkpoint] WARNING: cannot read metadata of {path} "
              f"({type(e).__name__}: {e})", file=sys.stderr)
        return None
    return None


def _check_meta(path: str, meta: Optional[dict],
                layout: Optional[str]) -> None:
    """Fail loudly on a version or layout mismatch; tolerate untagged
    (pre-version) checkpoints so old artifacts keep loading."""
    if meta is None:
        return
    version = int(meta.get("version", 0))
    if version > CHECKPOINT_VERSION:
        raise RuntimeError(
            f"checkpoint at {path} has format version {version}, this "
            f"build understands <= {CHECKPOINT_VERSION} — refusing to "
            f"reinterpret a newer checkpoint")
    saved_layout = meta.get("layout")
    if layout is not None and saved_layout is not None \
            and saved_layout != layout:
        raise RuntimeError(
            f"checkpoint at {path} was written with layout "
            f"{saved_layout!r} but the resuming executor carries X as "
            f"{layout!r} — resuming would silently permute rows; "
            f"rebuild the executor with the checkpointing mode or "
            f"delete the checkpoint")


def save_state(path: str, x: jax.Array, step: int,
               layout: Optional[str] = None) -> None:
    """Write {x, step} under ``path`` (a directory), atomically.

    ``layout`` tags the checkpoint with how X is carried (e.g.
    ``"multi_level/flat"``); load_state verifies it so a resume under a
    different execution mode fails loudly.
    """
    path = os.path.abspath(path)
    ocp = _orbax()
    if ocp is not None:
        # orbax's force=True deletes the old checkpoint before it writes
        # the new one, so a kill mid-save would lose both.  Write beside
        # it, then swap: the previous state survives as ``.prev`` until
        # the new one is in place (load_state falls back to it).
        staging, prev = path + ".saving", path + ".prev"
        ckpt = ocp.PyTreeCheckpointer()
        ckpt.save(staging, {"x": x, "step": np.int64(step)}, force=True)
        if jax.process_index() == 0:
            if os.path.isdir(path):
                shutil.rmtree(prev, ignore_errors=True)
                os.rename(path, prev)
            os.rename(staging, path)
            _write_meta(path, step, layout)
            shutil.rmtree(prev, ignore_errors=True)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"save_state:{path}")
        return
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    from arrow_matrix_tpu.parallel.mesh import fetch_replicated

    x_host = fetch_replicated(x)   # collective: every process joins
    if jax.process_count() == 1:
        tmp = path + ".tmp.npz"
        np.savez(tmp, x=x_host, step=np.int64(step),
                 version=np.int64(CHECKPOINT_VERSION),
                 layout=np.str_(layout or ""))
        os.replace(tmp, path + ".npz")
        # sha256 sidecar AFTER the npz replace: a crash between the
        # two leaves a stale sidecar that fails verification loudly
        # (never a silently-wrong state), and the fault-injection kill
        # scenarios land at step hooks, never inside this window.
        _write_sha(path + ".npz")
        return
    # Multi-process: one writer; its OUTCOME is broadcast, not
    # re-verified by peers re-reading the file — a re-read assumes a
    # shared filesystem and turns per-host local disks (or stale NFS
    # attribute caches) into a hard, misleadingly-worded failure on
    # every successful save.  NOTE the npz fallback still requires a
    # shared filesystem for peers to *load* the checkpoint later
    # (load_state reads path on each process); only the save-time
    # verification is FS-independent.  The allgather doubles as the
    # completion barrier: a caller loading right after save_state
    # returns cannot race process 0's os.replace.
    write_err: Exception | None = None
    outcome_step = np.int64(step)
    if jax.process_index() == 0:   # one writer
        try:
            tmp = path + ".tmp.npz"
            np.savez(tmp, x=x_host, step=np.int64(step),
                     version=np.int64(CHECKPOINT_VERSION),
                     layout=np.str_(layout or ""))
            os.replace(tmp, path + ".npz")
            _write_sha(path + ".npz")
        except Exception as e:   # noqa: BLE001 — ANY writer failure
            # (OSError, MemoryError, zipfile errors...) must still
            # reach the allgather below, or every peer deadlocks at a
            # collective the writer never joins.
            write_err = e
            outcome_step = np.int64(-1)
    from jax.experimental import multihost_utils

    outcome = np.asarray(
        multihost_utils.process_allgather(outcome_step)).reshape(-1)
    if int(outcome[0]) != step:
        # A failed writer must fail EVERY process, not leave peers
        # believing a stale checkpoint is current.
        raise RuntimeError(
            f"checkpoint write failed on process 0 "
            f"(write outcome {int(outcome[0])} != saved step {step})"
        ) from write_err


def load_state(path: str, like: Optional[jax.Array] = None,
               layout: Optional[str] = None
               ) -> Optional[tuple[jax.Array, int]]:
    """Read {x, step} from ``path``; None when absent.

    ``like`` (the freshly initialized feature array of the resuming
    executor) provides the expected shape/dtype/sharding: orbax
    restores each shard directly to its device; shape mismatches raise
    (an executor built differently from the checkpointing one must not
    silently reinterpret rows).  ``layout`` is verified against the tag
    the checkpoint was saved with (both paths); untagged pre-version
    checkpoints skip the check.
    """
    path = os.path.abspath(path)
    ocp = _orbax()
    if not os.path.exists(path) and os.path.isdir(path + ".prev"):
        path += ".prev"   # a save was cut between its two renames
    if os.path.isdir(path) and ocp is None:
        raise RuntimeError(
            f"checkpoint at {path} was written with orbax, which is not "
            f"importable here — silently restarting from iteration 0 "
            f"would discard it; install orbax or delete the directory")
    if ocp is not None and os.path.isdir(path):
        _check_meta(path, _read_meta(path), layout)
        ckpt = ocp.PyTreeCheckpointer()
        if like is not None:
            restore_args = ocp.ArrayRestoreArgs(sharding=like.sharding,
                                                dtype=like.dtype)
            out = ckpt.restore(
                path, restore_args={"x": restore_args, "step": None})
        else:
            out = ckpt.restore(path)
        x, step = out["x"], int(out["step"])
    elif os.path.exists(path + ".npz"):
        _verify_sha(path + ".npz")
        with np.load(path + ".npz") as z:
            meta = None
            if "version" in z.files:
                saved_layout = str(z["layout"]) if "layout" in z.files \
                    else ""
                meta = {"version": int(z["version"]),
                        "layout": saved_layout or None}
            _check_meta(path, meta, layout)
            x, step = z["x"], int(z["step"])
        if like is not None:
            from arrow_matrix_tpu.parallel.mesh import put_global

            x = put_global(np.asarray(x, dtype=like.dtype),
                           like.sharding)
    else:
        return None
    if like is not None and tuple(x.shape) != tuple(like.shape):
        raise ValueError(
            f"checkpoint X has shape {tuple(x.shape)}, executor expects "
            f"{tuple(like.shape)} — resume with the same mode/format/"
            f"devices the checkpoint was written with")
    from arrow_matrix_tpu.obs import flight

    flight.record("heal", "resumed", path=path, step=step,
                  layout=layout)
    return x, step
