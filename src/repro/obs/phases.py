"""Names of the phases of a factor modification (DESIGN.md §13).

The program wraps each phase of a dense fused modification in a
``jax.named_scope`` of one of these names. A scope is trace-time metadata
only: it lands in the ``op_name`` of every HLO instruction the phase emits
and adds no operation, so the compiled program is the same with or
without it. A profiler trace joins each device op to its instruction's
``op_name`` and so to its phase (``bench/phasereduce.py``).

* ``PAD`` — the factor and the rows padded to whole panels, and the rows
  transposed into the kernel's ``V^T`` operand;
* ``KERNEL`` — the fused kernel's ``pallas_call``;
* ``UNPAD`` — the kernel's output cut back to its upper triangle and to
  the factor's order;
* ``GUARD`` — what ``CholFactor.downdate_guarded`` adds to the downdate:
  the feasibility verdict and the select between the old and new factor.

Stdlib-only at import, like the rest of ``repro.obs``.
"""
from __future__ import annotations

PAD = "repro.pad"
KERNEL = "repro.kernel"
UNPAD = "repro.unpad"
GUARD = "repro.guard"

#: Every phase, in the order a guarded downdate runs them.
PHASES = (PAD, KERNEL, UNPAD, GUARD)


def scope(name: str):
    """The ``jax.named_scope`` of one phase (a context manager)."""
    import jax

    return jax.named_scope(name)
