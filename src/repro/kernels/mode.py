"""Interpret-vs-compiled selection for every Pallas kernel.

Kernels run compiled by Mosaic on a TPU and in Pallas interpret mode on
every other backend. The choice is made from ``jax.default_backend()``
when a kernel traces, never at import, so importing the package does not
initialise a backend. :func:`interpret_mode` is the one explicit,
scoped override (tests use it; so does ``CompiledCNN`` when its spec
pins ``interpret``).
"""
from __future__ import annotations

import contextlib
from typing import Iterator, List, Optional

import jax

_OVERRIDES: List[bool] = []       # innermost interpret_mode() flag last


def backend_interprets() -> bool:
    """True unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def interpret_mode(flag: bool = True) -> Iterator[None]:
    """Scoped interpret-vs-hardware selection for every kernel wrapper.

    ``with interpret_mode(True): ...`` runs the Pallas kernels in
    interpret mode inside the block and restores the previous mode on
    exit (exception-safe). The wrappers in :mod:`repro.kernels.ops` key
    their jit caches on the resolved mode, so a trace taken under one
    mode is never reused under the other.
    """
    _OVERRIDES.append(bool(flag))
    try:
        yield
    finally:
        _OVERRIDES.pop()


def get_interpret() -> bool:
    """The interpret flag kernels trace with right now."""
    return _OVERRIDES[-1] if _OVERRIDES else backend_interprets()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """An explicit flag wins; ``None`` follows :func:`get_interpret`."""
    return get_interpret() if interpret is None else bool(interpret)
