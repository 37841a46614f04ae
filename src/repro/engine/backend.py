"""Backend probing + legal-tile arithmetic — the engine's leaf module.

Deliberately dependency-free (os + jax only) so that *both* sides of the
stack can import it without cycles: ``kernels/ops.py`` (which the engine
registry wraps) and the engine's own registry/tuner/cache modules.

Before the engine plane, backend sniffing lived in two places with two
spellings — ``kernels/ops.py _on_tpu()`` (the interpret-mode switch) and
the plan heuristic's ``jax.default_backend()`` call (kernel-path
selection). They could never disagree in practice, but nothing *made* them
agree, and neither was overridable — CI could not pin plan selection on a
machine whose real backend differs from the one under test. ``backend()``
is now the single probe, honoring ``REPRO_FORCE_BACKEND``.
"""
from __future__ import annotations

import os

import jax

#: env override for backend probing ("cpu" | "tpu" | "gpu"). Forcing "tpu"
#: on a CPU host pins *plan selection* (scan="pallas", interpret=False
#: defaults) for deterministic tests — actually executing a forced-TPU plan
#: on CPU is the caller's (mis)use.
FORCE_BACKEND_ENV = "REPRO_FORCE_BACKEND"


def backend() -> str:
    """The platform plans are selected for: forced via env, else probed.

    The one backend probe for the whole stack — ``kernels/ops.py``'s
    interpret default, the heuristic's kernel-path choice, the tuner's
    search space and the plan-cache key all read this.
    """
    forced = os.environ.get(FORCE_BACKEND_ENV, "").strip().lower()
    if forced:
        return forced
    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


def default_interpret() -> bool:
    """Interpret-mode default: real Mosaic only on an (effective) TPU
    backend; everywhere else the Pallas bodies run the bit-exact Python
    interpreter."""
    return not on_tpu()


def resolve_interpret(interpret) -> bool:
    """Resolve an ``interpret=None`` request against the one backend probe.

    Every Pallas entry point (``kernels/ops.py`` wrappers AND the kernel
    modules' own jitted functions) funnels through this, so
    ``REPRO_FORCE_BACKEND`` governs interpret mode for all of them
    consistently. Must be called *outside* jit: the result becomes a
    static argument, and resolving inside a jitted function would freeze
    the env-dependent answer into the trace cache.
    """
    if interpret is None:
        return default_interpret()
    return bool(interpret)


def legal_tile(dim: int, requested: int, *, pow2: bool = False) -> int:
    """Largest legal tile for a dimension: the biggest divisor of ``dim``
    that is <= ``requested`` (and a power of two when the kernel demands
    it — ``dpxor``'s halving fold).

    This replaces the ``min(tile, dim)`` clamps that used to live in
    ``kernels/ops.py``: ``min`` silently produced *illegal* tiles whenever
    the clamp didn't divide the dimension (e.g. a non-power-of-two shard
    row count R=96 against the default 2048 yielded tile 96 — not a power
    of two — and the kernel raised deep inside ``pallas_call`` setup).
    """
    if dim <= 0:
        raise ValueError(f"dimension must be positive, got {dim}")
    if requested <= 0:
        raise ValueError(f"requested tile must be positive, got {requested}")
    cap = min(requested, dim)
    if pow2:
        # largest power of two that divides dim, capped at floor_pow2(cap)
        p2_of_dim = dim & -dim
        floor_p2 = 1 << (cap.bit_length() - 1)
        return min(p2_of_dim, floor_p2)
    if dim % cap == 0:
        return cap
    # enumerate divisors via trial division to sqrt(dim): dim is a row /
    # record count (<= 2^28 here), so this is thousands of iterations max
    best = 1
    d = 1
    while d * d <= dim:
        if dim % d == 0:
            if d <= cap:
                best = max(best, d)
            co = dim // d
            if co <= cap:
                best = max(best, co)
        d += 1
    return best
