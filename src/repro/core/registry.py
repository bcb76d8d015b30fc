"""Backend-pluggable op registry — the de-specialization mechanism.

The paper's thesis: the component library must not bake in one backend's
idioms.  Here every performance-critical op is *defined once* by name and
carries multiple lowerings:

* ``ref``    — pure ``jnp`` (the "portable C++"); always present, is the
  numerics oracle.
* ``pallas`` — the TPU-specialized kernel (``pl.pallas_call`` + BlockSpec).
* further backends (``pallas_interpret`` for CPU validation) register the
  same way — this is how Bambu slots in next to Vivado in the paper.

Selection: explicit argument > ambient ``use_backend(...)`` context >
global default.  Unknown (op, backend) pairs fall back to ``ref`` when
``allow_fallback`` — portability means degrading to the portable
implementation, never failing.  :func:`resolve` names the lowering a
call would get, so an entry point can print what it runs instead of
degrading in silence.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Dict, Optional

__all__ = ["register_op", "get_impl", "resolve", "use_backend",
           "current_backend", "set_default_backend", "list_ops"]

_OPS: Dict[str, Dict[str, Callable]] = {}
_state = threading.local()
_DEFAULT_BACKEND = "ref"


def register_op(name: str, backend: str = "ref"):
    """Decorator: register ``fn`` as the ``backend`` lowering of op ``name``."""
    def deco(fn):
        _OPS.setdefault(name, {})[backend] = fn
        return fn
    return deco


def set_default_backend(backend: str) -> None:
    global _DEFAULT_BACKEND
    _DEFAULT_BACKEND = backend


def current_backend() -> str:
    return getattr(_state, "backend", None) or _DEFAULT_BACKEND


@contextlib.contextmanager
def use_backend(backend: str):
    """Ambiently select a backend for all ops in scope."""
    prev = getattr(_state, "backend", None)
    _state.backend = backend
    try:
        yield
    finally:
        _state.backend = prev


def resolve(name: str, backend: Optional[str] = None, *,
            allow_fallback: bool = True) -> str:
    """The backend whose lowering ``get_impl(name, backend)`` returns."""
    if name not in _OPS:
        raise KeyError(f"op {name!r} is not registered")
    b = backend or current_backend()
    impls = _OPS[name]
    if b in impls:
        return b
    if allow_fallback and "ref" in impls:
        return "ref"
    raise KeyError(f"op {name!r} has no {b!r} lowering and fallback is off "
                   f"(available: {sorted(impls)})")


def get_impl(name: str, backend: Optional[str] = None, *,
             allow_fallback: bool = True) -> Callable:
    return _OPS[name][resolve(name, backend, allow_fallback=allow_fallback)]


def list_ops() -> Dict[str, list]:
    return {k: sorted(v) for k, v in _OPS.items()}
