"""Kernel-backend selection for the sparse subsystem.

One switch replaces every ``use_kernel=`` / ``interpret=`` flag that used to
be threaded through call sites:

  "pallas" — the Pallas kernels: compiled on a TPU, Pallas interpret mode
             off it (``kernels.ops.interpret_mode`` decides, in one place)
  "ref"    — the pure-jnp reference formulations (XLA fuses them; this is
             what the dry-run lowers)
  "auto"   — resolve to "pallas". Never to "ref": the reference path runs
             only when a caller asks for it by name

The default is configured once — on a ``SparsityPolicy``/``SparsityPlan``,
on a format call, or process-wide with ``set_default_backend`` /
``use_backend`` — instead of at every matvec.
"""
from __future__ import annotations

import contextlib
import warnings

__all__ = ["BACKENDS", "resolve", "set_default_backend",
           "get_default_backend", "use_backend", "from_use_kernel"]

BACKENDS = ("auto", "pallas", "ref")

_default = "auto"


def get_default_backend() -> str:
    return _default


def set_default_backend(backend: str) -> None:
    global _default
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    _default = backend


@contextlib.contextmanager
def use_backend(backend: str):
    """Scoped override of the process default backend."""
    prev = get_default_backend()
    set_default_backend(backend)
    try:
        yield
    finally:
        set_default_backend(prev)


def resolve(backend: str | None = None) -> str:
    """Resolve a per-call backend to concrete "pallas" or "ref".

    Parameters
    ----------
    backend : {"pallas", "ref", "auto", None}
        Per-call request. None and "auto" both defer to the configured
        process default, so ``set_default_backend``/``use_backend`` reach
        every policy/plan left at backend="auto". A default of "auto"
        resolves to "pallas"; "ref" is returned only when asked for.

    Returns
    -------
    str
        Concrete ``"pallas"`` or ``"ref"``.

    Examples
    --------
    >>> resolve("ref")
    'ref'
    >>> resolve("pallas")
    'pallas'
    >>> resolve(None) in ("pallas", "ref")
    True
    """
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    b = _default if backend in (None, "auto") else backend
    return "pallas" if b == "auto" else b


def from_use_kernel(use_kernel: bool, *, stacklevel: int = 3) -> str:
    """Adapter for the deprecated ``use_kernel=`` boolean."""
    warnings.warn(
        "use_kernel= is deprecated; pass backend='pallas'|'ref'|'auto' "
        "(see repro.sparse.backend)", DeprecationWarning,
        stacklevel=stacklevel)
    return "pallas" if use_kernel else "ref"
