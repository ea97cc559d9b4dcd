"""Shared dispatch + padding helpers for every ``kernels/*`` op wrapper.

Each kernel package used to carry its own copy of the backend probe and the
tile-padding helpers; they are deduplicated here so the dispatch contract is
stated (and regression-tested) once:

* ``resolve_interpret(None)`` -> run the Pallas body through the interpreter
  exactly when the backend is not a TPU (the correctness path for kernels
  with no XLA ref); an explicit bool always wins.  Used by the layout
  kernels (flash/decode attention, moe_gmm, rglru_scan).
* ``dispatch_pallas(None)`` -> run the Pallas kernel only on TPU; off-TPU
  the op compiles its pure-jnp ref through XLA instead of falling into the
  slow interpreter.  An explicit ``interpret`` bool forces the Pallas body
  (kernel-validation tests).  Used by the selection kernels
  (``dsqe_score``, ``retrieval_topk``) which ship a ref with identical
  decision semantics.

Padding policy (the fill contract audited by ``tests/test_kernels.py``):
zero-fill is only legal where the padded elements are *masked before any
score comparison* (an in-kernel ``iota < n_valid -> NEG_INF`` guard, a
``valid == 0`` lane mask, or the row being sliced off before decode).
Anywhere a padded row/lane could reach a top-k or argmax unmasked, the fill
must itself be losing (``-inf`` / ``NEG_INF``) — a zero-filled pad row beats
every real candidate the moment all real scores go negative.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Masked-score sentinel shared by the selection kernels and their refs.
# Finite (not -inf) so masked lanes never poison reductions with NaNs via
# inf - inf; anything below NEG_INF / 2 is "masked", anything above is real.
NEG_INF = -1e30

# Selection is a float32 contract (``core/rps.py``).  On a TPU a float32 dot
# at DEFAULT precision is one bfloat16 pass, enough to move kNN membership
# and argmax decisions, so every selection dot asks for full float32.  On a
# TPU v5e, 128 queries x 100,352 rows x 256-d, top-16: DEFAULT put 155 of
# the 2,048 ids wrong beyond float64 near-ties (score error up to 6.2e-4),
# HIGHEST none (error 6.5e-8).
SELECT_PRECISION = jax.lax.Precision.HIGHEST


def is_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret-mode policy for kernels without an XLA ref dispatch:
    ``None`` means interpret everywhere except TPU (correctness path);
    an explicit bool is honored as-is."""
    return (not is_tpu()) if interpret is None else bool(interpret)


def dispatch_pallas(interpret: bool | None) -> bool:
    """Dispatch policy for kernels WITH an XLA ref: should the Pallas
    kernel run at all?  ``None`` -> only on TPU (off-TPU the op returns its
    jitted ref instead); any explicit bool -> yes, with that interpret
    setting (``bool(None)`` is never reached off this gate)."""
    return interpret is not None or is_tpu()


def pad2(x: jax.Array, m0: int, m1: int, fill: float = 0.0) -> jax.Array:
    """Pad a 2-D array up to (multiple of m0, multiple of m1) with ``fill``.

    Callers own the masking obligation in the module docstring: zero-fill
    demands a downstream mask/slice before any score comparison."""
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=fill)
    return x


def pad_dim(x: jax.Array, axis: int, mult: int,
            fill: float = 0.0) -> tuple[jax.Array, int]:
    """Pad one axis up to a multiple of ``mult``; returns (padded, original
    size) so callers can slice the result back."""
    size = x.shape[axis]
    pad = (-size) % mult
    if not pad:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill), size
