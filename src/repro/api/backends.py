"""Backend dispatch for plan execution — three backends, one switch:

* ``"pallas"``    — compiled Pallas kernels (TPU).
* ``"interpret"`` — the same Pallas kernels in interpret mode (CPU-correct;
  the default off-TPU so tests and laptops just work).
* ``"reference"`` — the pure-jnp oracles from :mod:`repro.kernels.ref`
  (differentiable everywhere; the parity baseline).

The default resolves from the JAX platform once, can be overridden globally
(:func:`set_default_backend`) or lexically (:func:`use_backend`).  Backend
choice is resolved at trace time: functions jitted under ``use_backend`` bake
the choice into their compiled executable.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional, Tuple

import jax

BACKENDS: Tuple[str, ...] = ("pallas", "interpret", "reference")

#: Lane width of the TPU's vector registers.  On the compiled backend every
#: block dimension and every N-tile is a multiple of it: Mosaic refuses the
#: A-tile DMA of a 64-wide block ("Slice shape … must be aligned to tiling
#: (128)") and any B/C window narrower than 128 columns.
LANE = 128


class BlockShapeError(ValueError):
    """A block shape the compiled ``pallas`` backend cannot run."""


def check_block_shape(block_shape, backend: str) -> None:
    """Refuse block dimensions that are not multiples of :data:`LANE` on the
    compiled backend; ``interpret`` and ``reference`` take any block."""
    if backend == "pallas" and any(int(d) % LANE for d in block_shape):
        raise BlockShapeError(
            f"block shape {tuple(int(d) for d in block_shape)} cannot run "
            f"on backend='pallas': every block dimension must be a multiple "
            f"of {LANE} (the TPU lane width); use 128-wide blocks, or the "
            f"'interpret'/'reference' backend for smaller ones")

_default_backend: Optional[str] = None


def _platform_default() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "interpret"


def available_backends() -> Tuple[str, ...]:
    return BACKENDS


def default_backend() -> str:
    """The backend used when none is passed explicitly."""
    return _default_backend if _default_backend is not None else _platform_default()


def set_default_backend(name: Optional[str]) -> None:
    """Set the process-wide default backend (``None`` restores autodetect)."""
    global _default_backend
    if name is not None:
        resolve_backend(name)
    _default_backend = name


def resolve_backend(name: Optional[str]) -> str:
    """Validate ``name`` (or resolve the default when ``None``)."""
    if name is None:
        return default_backend()
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; available: {BACKENDS}")
    return name


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[str]:
    """Lexically scope the default backend (e.g. force ``reference`` in a
    parity test, or ``interpret`` while tracing a serving function on CPU)."""
    global _default_backend
    name = resolve_backend(name)
    prev = _default_backend
    _default_backend = name
    try:
        yield name
    finally:
        _default_backend = prev


def backend_interpret_flag(name: str) -> bool:
    """Map a pallas-family backend to the kernel ``interpret`` flag."""
    if name == "reference":
        raise ValueError("reference backend does not run Pallas kernels")
    return name == "interpret"
