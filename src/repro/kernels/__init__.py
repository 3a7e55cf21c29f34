"""Pallas kernel package + accelerator backend probe.

The kernels are the hot-spot implementations of the compute the paper
itself optimizes with custom kernels (bitplane pack/unpack, plane split,
fused encode, fused decode+reduce, rANS).  ``ops.py`` is the public
dispatch layer; every entry point takes ``use_pallas``/``interpret`` knobs,
and every kernel module resolves ``interpret=None`` through
:func:`resolve_interpret`.

Backend probe: interpret-mode Pallas is CPU-slow, so off the TPU the
collectives use the pure-jnp reference.  :func:`default_use_pallas` turns
the compiled Pallas path on when the TPU backend is present (and only
there); callers pass ``use_pallas=None`` to opt into the probe.  A backend
that fails to start raises: it is never reported as the CPU.
``REPRO_USE_PALLAS=1`` forces the kernels on off-TPU (interpret mode, for
tests); on the TPU a false value is an error, so a chip run never silently
runs the reference.  The sched plan compiler records the probed backend in
every ``CommPlan`` so a compiled plan documents which dispatch it drives.
"""
from __future__ import annotations

import functools
import logging
import os

import jax

from repro import obs

_TRUTHY = ("1", "true", "True", "yes", "on")

_logger = logging.getLogger("repro.kernels")

# ---------------------------------------------------------------------------
# Dispatch-fallback accounting.  The fast paths gate on shape conditions
# (tile-multiple for the Pallas kernels, block-multiple chunks for the
# fused chunked encode); when a caller requested the fast path but the
# gate routes to a fallback implementation, the degrade used to be silent —
# plans recorded use_pallas=True while real (ragged) model shapes quietly
# ran the reference.  Every degradation now lands here: counted per op (so
# benchmarks/plans can report effective dispatch coverage) and logged ONCE
# per op (so a million-step run doesn't spam).
# ---------------------------------------------------------------------------

_FALLBACKS: dict = {}
_FALLBACK_WARNED: set = set()


def record_fallback(op: str, reason: str) -> None:
    """Count (and log once per op) a fast-path dispatch degrade."""
    _FALLBACKS[op] = _FALLBACKS.get(op, 0) + 1
    obs.metric("kernel_fallback_total").inc(op=op)
    if op not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(op)
        _logger.warning(
            "kernels.%s: fast path unavailable (%s) — dispatching to the "
            "fallback implementation; further fallbacks counted silently "
            "(kernels.fallback_counts())", op, reason)


def fallback_counts() -> dict:
    """Per-op count of fast-path dispatch degrades since the last clear."""
    return dict(_FALLBACKS)


def clear_fallbacks() -> None:
    _FALLBACKS.clear()
    _FALLBACK_WARNED.clear()


@functools.lru_cache(maxsize=None)
def backend() -> str:
    """The active jax backend platform name ("cpu" | "gpu" | "tpu")."""
    return jax.default_backend()


def has_tpu() -> bool:
    return backend() == "tpu"


@functools.lru_cache(maxsize=None)
def default_use_pallas() -> bool:
    """True iff Pallas kernels should be used by default on this backend.

    TPU: compiled Pallas is the hot-spot implementation — always on.
    CPU/GPU: only interpret mode exists here — off (pure-jnp reference,
    which XLA fuses well) unless ``REPRO_USE_PALLAS`` is truthy."""
    env = os.environ.get("REPRO_USE_PALLAS")
    if has_tpu():
        if env is not None and env not in _TRUTHY:
            raise RuntimeError(
                f"REPRO_USE_PALLAS={env!r} on the TPU backend: the chip path "
                "always runs the compiled kernels; unset it")
        return True
    return env is not None and env in _TRUTHY


def default_interpret() -> bool:
    """Interpret mode for Pallas calls: compiled on real TPU, interpreted
    everywhere else (the only mode available off-TPU)."""
    return not has_tpu()


def resolve_use_pallas(use_pallas) -> bool:
    """None -> probe; explicit bool wins."""
    return default_use_pallas() if use_pallas is None else bool(use_pallas)


def resolve_interpret(interpret) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def probe_cache_clear() -> None:
    """Reset the cached probe results (tests flip REPRO_USE_PALLAS)."""
    backend.cache_clear()
    default_use_pallas.cache_clear()
