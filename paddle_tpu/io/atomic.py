"""Crash-safe file-write discipline — ONE implementation.

Every durable artifact in this repo (checkpoints, flight-recorder
dumps, the fleet router's write-ahead journal segments) follows the
same three rules, extracted here so the discipline cannot drift
between subsystems:

- **atomic replace**: payload bytes land in a ``<path>.tmp`` sibling,
  are fsynced, and only then ``os.replace``d onto the final name — a
  reader can observe the old file or the new file, never a torn one.
  The parent directory is fsynced after the rename so the *name*
  itself survives a power cut (best-effort on filesystems that
  refuse directory fds).
- **COMPLETE marker**: multi-file artifacts (checkpoint step dirs,
  journal segments) additionally write a small marker file strictly
  AFTER the payload is in place; consumers treat only marked
  artifacts as finalized, so a crash at ANY byte of a save costs that
  save, never the ability to read an older one
  (docs/robustness.md "Crash-safe checkpoints").
- **never clobber**: postmortem artifacts (flight dumps) pick a fresh
  numbered name instead of overwriting an earlier incident's record.

Stdlib-only by contract: paddle_tpu.observability.flightrec loads
this module straight from its file in the stdlib-only tools (see
tools/_obs.py), so nothing here may import jax, numpy, or any
sibling package.
"""
from __future__ import annotations

import json
import os

__all__ = ["MARKER_NAME", "atomic_replace", "fsync_dir", "marker_path",
           "publish_dir", "unique_path", "write_marker"]

#: canonical marker filename for directory-shaped artifacts
#: (checkpoint step dirs); file-shaped artifacts (journal segments)
#: use ``<file>.complete`` sidecars via marker_path().
MARKER_NAME = "COMPLETE"


def fsync_dir(path):
    """Best-effort fsync of a DIRECTORY, making a just-renamed entry
    durable. Some filesystems (and some containerized mounts) refuse
    O_DIRECTORY opens — the rename itself is still atomic there, so
    failure is swallowed, not raised."""
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return False
    try:
        os.fsync(fd)
        return True
    except OSError:
        return False
    finally:
        os.close(fd)


def atomic_replace(path, data, fsync=True):
    """Write `data` (bytes or str) to `path` atomically: tmp sibling,
    optional fsync, os.replace, parent-dir fsync. Returns `path`.
    A crash anywhere leaves either the previous file or the new one —
    never a prefix."""
    if isinstance(data, str):
        data = data.encode("utf-8")
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(data)
        if fsync:
            f.flush()
            os.fsync(f.fileno())
    os.replace(tmp, path)
    if fsync:
        fsync_dir(os.path.dirname(os.path.abspath(path)))
    return path


def marker_path(target):
    """The COMPLETE-marker path for an artifact: ``<dir>/COMPLETE``
    for a directory, ``<file>.complete`` sidecar for a file."""
    if os.path.isdir(target):
        return os.path.join(target, MARKER_NAME)
    return target + ".complete"


def write_marker(path, meta=None, fsync=True):
    """Write a finalize marker at `path` (use marker_path() to derive
    it) carrying `meta` as JSON. fsynced by default — the marker IS
    the durability claim, so it must not itself be lost to a cut."""
    with open(path, "w") as f:
        json.dump(meta or {}, f)
        if fsync:
            f.flush()
            os.fsync(f.fileno())
    if fsync:
        fsync_dir(os.path.dirname(os.path.abspath(path)))
    return path


def has_marker(target):
    return os.path.exists(marker_path(target))


def publish_dir(staging, final, fsync=True):
    """Atomically publish a fully-staged DIRECTORY artifact: fsync
    every regular file in `staging` (a crash after the rename must not
    reveal torn payload bytes under the final name), rename it onto
    `final`, fsync the parent, then write the COMPLETE marker strictly
    last. A crash at ANY point leaves either no `final` entry or an
    unmarked one — consumers that require the marker (has_marker) can
    never load a half-written artifact. `final` must not already
    exist (callers stage into a sibling and pick fresh names; this is
    the never-clobber rule for directory artifacts). Returns `final`.
    """
    if fsync:
        for base, _dirs, files in os.walk(staging):
            for name in files:
                fd = os.open(os.path.join(base, name), os.O_RDONLY)
                try:
                    os.fsync(fd)
                finally:
                    os.close(fd)
            fsync_dir(base)
    os.rename(staging, final)
    parent = os.path.dirname(os.path.abspath(final))
    if fsync:
        fsync_dir(parent)
    write_marker(marker_path(final), {"published": True}, fsync=fsync)
    return final


def unique_path(directory, stem, ext=".json"):
    """A fresh ``<dir>/<stem><ext>`` that never clobbers an existing
    file (numeric ``_2``, ``_3``... suffixes). `stem` is sanitized to
    [alnum - _] so an arbitrary reason string cannot escape the dir."""
    safe = "".join(c if (c.isalnum() or c in "-_") else "_"
                   for c in str(stem)) or "unknown"
    path = os.path.join(directory, f"{safe}{ext}")
    n = 2
    while os.path.exists(path):
        path = os.path.join(directory, f"{safe}_{n}{ext}")
        n += 1
    return path
