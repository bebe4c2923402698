"""Donated-buffer safety registry.

Buffer donation (``to_static(donate=True)`` / the Engine's donated train
step) lets XLA reuse the parameter/optimizer-state input HBM for the
updated outputs — the memory win that buys bigger batches. The hazard is
the stale reference: after a donating call the OLD device buffers are
invalid, and anything still holding one (a Tensor captured before the
step, a params list the caller kept) would die inside XLA with an opaque
"Array has been deleted". This registry upgrades that to the framework's
own error, naming the donation site.

Zero-cost discipline: ``check()`` is one dict lookup while no donation
has ever happened in the process; donating callers ``mark_donated()``
the buffers they invalidated (bounded id→context map, newest wins).
"""
from __future__ import annotations

from typing import Iterable, Optional

__all__ = ["DonatedBufferError", "mark_donated", "active", "check",
           "ensure_distinct", "ensure_live", "watch_reads"]

#: hot mirror: False until the first donating call in this process, so
#: the Tensor host-read paths pay one dict lookup and nothing else
_state = {"on": False}
#: donated buffer id -> context string (bounded; ids recycle with GC, so
#: this is best-effort naming — is_deleted() is the ground truth)
_contexts: dict = {}
_CONTEXTS_MAX = 1024


class DonatedBufferError(RuntimeError):
    """A buffer invalidated by donation was used again. The fix is to
    read state through its owner (the Parameter / the step's returned
    arrays), which the donating caller re-binds after every call — not
    through references captured before the donating step ran."""


def active() -> bool:
    return _state["on"]


#: host-read observation seam: the program verifier (static.verifier)
#: installs a callback here while it traces a donating step, so it can
#: flag donated-then-host-read hazards STATICALLY — before the runtime
#: path below ever sees a stale buffer. One dict lookup when unused.
_watch = {"cb": None}


class watch_reads:
    """Context manager observing every Tensor host-read that flows
    through :func:`check` (numpy/item/tolist/__array__/cpu). The
    callback receives ``(array, site)``; it must never raise."""

    def __init__(self, cb):
        self._cb = cb

    def __enter__(self):
        self._prev = _watch["cb"]
        _watch["cb"] = self._cb
        return self

    def __exit__(self, *exc):
        _watch["cb"] = self._prev
        return False


def mark_donated(arrays: Iterable, context: str):
    """Record buffers a donating call just invalidated. ``context``
    names the call site for the eventual error message."""
    _state["on"] = True
    for a in arrays:
        if len(_contexts) >= _CONTEXTS_MAX:
            _contexts.pop(next(iter(_contexts)))
        _contexts[id(a)] = context


def _is_deleted(arr) -> bool:
    fn = getattr(arr, "is_deleted", None)
    try:
        return bool(fn()) if fn is not None else False
    except Exception:
        return False


def check(arr, site: str = "this read"):
    """Raise :class:`DonatedBufferError` if ``arr`` is a deleted device
    buffer and any donation has happened; no-op (two dict lookups)
    otherwise."""
    w = _watch["cb"]
    if w is not None:
        w(arr, site)
    if not _state["on"]:
        return
    if _is_deleted(arr):
        ctx = _contexts.get(id(arr), "a donated compiled step")
        raise DonatedBufferError(
            f"{site} touches a device buffer that was donated by "
            f"{ctx} and no longer holds data. Donation hands the "
            f"buffer's HBM to the step's outputs; re-read the value "
            f"through its owning Parameter / the step's returned "
            f"arrays instead of a reference captured before the "
            f"donating call.")


def ensure_live(arrays: Iterable, site: str):
    """Entry guard of donating calls: every argument buffer must still
    be live — feeding a previously-donated array back in is the classic
    reuse bug."""
    for a in arrays:
        check(a, site)


def ensure_distinct(pairs: Iterable, site: str):
    """Donation requires each donated leaf to be a DISTINCT buffer (XLA
    rejects one buffer donated twice with a runtime error deep in the
    launch). ``pairs`` is an iterable of (label, array)."""
    seen: dict = {}
    for label, a in pairs:
        prev = seen.get(id(a))
        if prev is not None:
            raise DonatedBufferError(
                f"{site}: {label!r} and {prev!r} share one device "
                f"buffer, which cannot be donated twice. Materialize "
                f"distinct copies (e.g. paddle.assign) before the "
                f"donating call.")
        seen[id(a)] = label
