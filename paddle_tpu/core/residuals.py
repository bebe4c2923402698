"""What a rematerialised block may keep for its backward, by name.

A forward rule marks an array with ``jax.ad_checkpoint.checkpoint_name``;
``models/_remat.py`` hands ``jax.checkpoint`` the names its policy saves.
Outside a ``jax.checkpoint`` a name is the identity.
"""
from __future__ import annotations

import contextlib
import threading

from jax.ad_checkpoint import checkpoint_name

#: the two residuals of a flash-attention call that the backward kernels read
#: and the forward kernel alone can make, as the forward rule names them:
#: ``out`` as (B, S, H, d), the array the block goes on with and the backward
#: kernels read in place, and the logsumexp as the forward kernel writes it,
#: lane-dense (B, H // hpb, hpb, S_padded) float32 rows. Every rematerialised
#: block keeps these beside its input, so its backward never runs the forward
#: kernel again.
KEPT_RESIDUALS = ("flash_out", "flash_lse")

#: the result of an ``F.linear`` product (after the bias, in the dtype autocast
#: gave it), named only while a ``kept_residuals()`` block is open: a
#: rematerialised block keeps these too while the program's budget lasts
#: (``models/_remat.py`` ``KEPT_SHARE``), and its backward then reads the
#: projection's output where it would run the product a second time. Named is
#: not kept: an output nothing in the backward reads (a block's last
#: projection) is saved by no policy.
LINEAR_OUT = "linear_out"


class _Kept(threading.local):
    """This thread's list of what the forward rules named, while a
    ``kept_residuals()`` block is open."""
    log = None


_kept = _Kept()


@contextlib.contextmanager
def kept_residuals():
    """``[(name, shape, dtype), ...]`` of the residuals the forward rules
    named while the body ran (``remat_block`` reports them)."""
    outer, _kept.log = _kept.log, []
    try:
        yield _kept.log
    finally:
        _kept.log = outer


def is_open() -> bool:
    """Whether a ``kept_residuals()`` block is open on this thread."""
    return _kept.log is not None


def keep(x, name):
    """``x`` under ``name``, noted in the open block's list."""
    if _kept.log is not None:
        _kept.log.append((name, x.shape, x.dtype))
    return checkpoint_name(x, name)
