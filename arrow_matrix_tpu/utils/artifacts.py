"""Shared helpers over the JSON artifacts the bench, tuner and
serving tools write: the last-JSON-line child protocol, atomic
persistence, and file locking.
"""

from __future__ import annotations

import contextlib
import json
import os
import tempfile
import threading
from typing import Any, Optional

try:                            # POSIX; absent on some platforms —
    import fcntl                # locking degrades to a no-op there
except ImportError:             # pragma: no cover
    fcntl = None

from arrow_matrix_tpu import sync


def parse_last_json_line(text: str) -> Optional[dict]:
    """Parse the LAST line of ``text`` as a JSON object (bench children
    and JSON-lines artifacts both commit their record as the final
    line; anything above it — warnings, progress chatter — is noise).
    None when the text is empty, the last line is not JSON, or it is
    JSON but not an object — the caller decides what absence means."""
    try:
        d = json.loads(text.strip().splitlines()[-1])
    except (json.JSONDecodeError, IndexError, AttributeError,
            TypeError):
        return None
    return d if isinstance(d, dict) else None


# ---------------------------------------------------------------------------
# Atomic JSON persistence (graft-ledger satellite).
#
# Five modules grew their own tmp-file + os.replace copy of "write the
# artifact atomically" (tune/plan.py, serve/loadgen.py, obs/pulse.py,
# obs/flight.py, io/graphio.py) — none of which fsync'd, so a host
# power-cut inside the page-cache window could land an EMPTY tmp file
# over a good artifact.  This is the ONE implementation they all share
# now, and the crash-window contract is explicit:
#
# * serialization happens BEFORE the target is touched — an
#   unserializable object leaves the existing artifact intact;
# * the tmp file lives in the target's directory (os.replace must not
#   cross filesystems) with a pid+thread-unique name, is flushed and
#   fsync'd before the rename, and the DIRECTORY is fsync'd after it —
#   the rename itself is not durable until the directory entry is;
# * any failure removes the tmp file and re-raises: the caller decides
#   whether persistence is best-effort (flight recorder, pulse ring)
#   or mandatory (tune plans, the ledger).


def _fsync_dir(directory: str) -> None:
    """Flush a directory entry (the rename durability half of an
    atomic write).  Platforms whose directories cannot be opened
    (Windows) skip — there the rename atomicity is all we get."""
    try:
        fd = os.open(directory or ".", os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def atomic_write_json(path: str, obj: Any, *, indent=None,
                      sort_keys: bool = False,
                      fsync: bool = True) -> str:
    """Atomically (and, by default, durably) write ``obj`` as JSON to
    ``path``; returns ``path``.  See the module comment for the
    crash-window contract.  ``fsync=False`` keeps the atomicity (a
    reader never sees a torn file) but trades the power-cut durability
    for speed — appropriate for high-frequency telemetry rewrites."""
    text = json.dumps(obj, indent=indent, sort_keys=sort_keys)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(
        dir=d or ".",
        prefix=f".{os.path.basename(path)}.{os.getpid()}."
               f"{threading.get_ident()}.",
        suffix=".tmp")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as fh:
            fh.write(text)
            if fsync:
                fh.flush()
                os.fsync(fh.fileno())
        os.replace(tmp, path)
        if fsync:
            _fsync_dir(d)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return path


def flock_acquire(handle, *, shared: bool = False,
                  nonblocking: bool = False) -> bool:
    """The package's single audited ``fcntl.flock`` call site — every
    flock discipline (the sidecar lock below) routes through here so
    graft-sync's RC2 can flag any raw call it cannot see.  ``handle`` is a file object
    or fd; returns whether the lock was taken (always True for a
    blocking acquire, and trivially True where ``fcntl`` is absent —
    locking degrades to a no-op there).  A nonblocking miss returns
    False instead of raising.  The lock is released when the handle is
    closed (the callers' existing discipline) — pair the held region
    with ``sync.flock_witness(<node>)`` so the runtime witness sees it.
    """
    if fcntl is None:           # pragma: no cover
        return True
    flags = fcntl.LOCK_SH if shared else fcntl.LOCK_EX
    if nonblocking:
        flags |= fcntl.LOCK_NB
    try:
        fcntl.flock(handle, flags)  # graft-sync: flock-primitive
    except OSError:
        if nonblocking:
            return False
        raise
    return True


@contextlib.contextmanager
def locked_file(path: str):
    """Advisory cross-process exclusive lock scoped to ``path``
    (graft-fleet satellite): ``fcntl.flock`` on a sidecar
    ``<path>.lock`` file, so N worker PROCESSES mutating one shared
    artifact — a tune-plan merge-write, a hash-chained ledger append —
    serialize instead of losing each other's updates.  The sidecar
    (not the artifact itself) is locked because the artifact is
    replaced by ``os.replace`` during atomic writes, which would
    orphan a lock held on the old inode.

    NOT reentrant: flock blocks between file descriptors even within
    one process, so a holder must not re-acquire (``append_jsonl``'s
    ``lock=False`` exists for exactly that).  On platforms without
    ``fcntl`` this degrades to a no-op — single-process behavior
    there is unchanged.
    """
    if fcntl is None:           # pragma: no cover
        yield
        return
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
    try:
        flock_acquire(fd)
        with sync.flock_witness("sidecar"):
            yield
    finally:
        os.close(fd)            # close releases the flock


def append_jsonl(path: str, obj: Any, *, fsync: bool = True,
                 lock: bool = True) -> str:
    """Append ``obj`` as one JSON line to ``path`` (created if absent);
    returns the serialized line.  The line is serialized before the
    file is opened and written in one call, then flushed and fsync'd —
    a crash can tear at most the line being appended (trailing partial
    line), never an earlier record: the append-only ledger's
    durability primitive.  The write holds the :func:`locked_file`
    advisory lock so two processes cannot interleave partial lines;
    callers already inside the lock (``Ledger.record`` serializes its
    read-chain-then-append critical section) pass ``lock=False``."""
    line = json.dumps(obj, sort_keys=False,
                      separators=(",", ":")) + "\n"
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    ctx = locked_file(path) if lock else contextlib.nullcontext()
    with ctx:
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(line)
            if fsync:
                fh.flush()
                os.fsync(fh.fileno())
    return line
