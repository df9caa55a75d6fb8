"""Precision policy: storage dtype vs accumulation dtype (DESIGN.md §8).

The paper is explicit that rank-k up/down-dating is *bandwidth-bound*
("limited speed ups are possible due to the bandwidth bound nature of the
problem"), so the bytes each off-diagonal L-tile occupies in HBM are the
dominant cost of an update. Halving them — bf16 tiles — is the single
biggest paper-aligned lever left, *provided* the numerically sensitive part
stays in fp32: the serial diagonal recurrence divides by the running
diagonal (``c = w / l_ii``) and chains k hyperbolic rotations per row, so
its rounding errors propagate into every trailing panel.

``Precision`` makes that split a first-class, validated policy:

* ``storage`` — the dtype L-tiles and the running ``V^T`` panels live in
  (in HBM between grid steps, and in the whole-launch VMEM scratch of the
  fused kernel). ``None`` means "whatever dtype the inputs already have" —
  the legacy behaviour, bit-for-bit.
* ``accum``   — the dtype every *computation* runs in: the diagonal
  recurrence, the transform ``T``, and GEMM accumulation
  (``preferred_element_type``). Always at least fp32; tangents/cotangents
  of the Murray derivative rules use it too.

This mirrors how the tall-skinny QR literature (Thies & Röhrig-Zöllner)
and Murray (2016) keep reductions/derivatives in higher precision than
storage. The policy is a frozen, hashable dataclass so it rides as static
aux on ``CholFactor`` and as a jit static argument through the registry.

The module is dependency-light on purpose (jax.numpy only): the blocked
drivers, all three kernel families, and the distributed driver import it
without touching the factor/api layer. ``repro.core.factor`` re-exports
``Precision`` as the user-facing home the rest of the docs point at.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Union

import jax.numpy as jnp
import numpy as np

PrecisionLike = Union[None, str, "Precision", Any]

# Named presets: the policies benchmarks, tests and CLIs spell by string.
_PRESETS = {
    "float32": ("float32", "float32"),
    "f32": ("float32", "float32"),
    "fp32": ("float32", "float32"),
    "bfloat16": ("bfloat16", "float32"),
    "bf16": ("bfloat16", "float32"),
    "float64": ("float64", "float64"),
    "f64": ("float64", "float64"),
    "highest": (None, "float64"),
}


def _as_dtype(spec) -> np.dtype:
    try:
        dt = np.dtype(jnp.dtype(spec))
    except TypeError as e:
        raise ValueError(f"not a dtype: {spec!r}") from e
    # jnp.issubdtype, not np.issubdtype: ml_dtypes (bfloat16, fp8) register
    # with JAX's extended lattice but are not numpy-floating subtypes.
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(f"precision dtypes must be floating, got {dt}")
    return dt


@dataclasses.dataclass(frozen=True)
class Precision:
    """Storage/accumulation dtype split for the rank-k modification.

    Attributes:
      storage: dtype the factor tiles and ``V^T`` panels are *stored* in
        between chain steps (None = keep the input dtype untouched).
      accum: dtype the recurrence/rotations/GEMMs *compute* in; must be at
        least as wide as ``storage`` and at least fp32.
    """

    storage: Optional[np.dtype] = None
    accum: np.dtype = np.dtype(np.float32)

    def __post_init__(self):
        storage = None if self.storage is None else _as_dtype(self.storage)
        accum = _as_dtype(self.accum)
        if accum.itemsize < np.dtype(np.float32).itemsize:
            raise ValueError(
                f"accum dtype must be at least float32, got {accum} — the "
                "diagonal recurrence divides by the running diagonal and is "
                "not stable in 16-bit arithmetic")
        if storage is not None and storage.itemsize > accum.itemsize:
            raise ValueError(
                f"storage dtype {storage} is wider than accum dtype {accum}; "
                "the policy is storage <= accum")
        object.__setattr__(self, "storage", storage)
        object.__setattr__(self, "accum", accum)

    # -- construction -------------------------------------------------------
    @classmethod
    def parse(cls, spec: PrecisionLike) -> Optional["Precision"]:
        """Canonicalise a user spec: None | preset str | dtype | Precision.

        ``None`` stays None (legacy behaviour: no casts anywhere). A bare
        dtype means "store in this dtype, accumulate in fp32-or-wider".
        """
        if spec is None or isinstance(spec, cls):
            return spec
        if isinstance(spec, str):
            key = spec.lower()
            if key in _PRESETS:
                storage, accum = _PRESETS[key]
                return cls(storage=storage, accum=accum)
            # fall through: maybe a dtype string like 'float16'
        storage = _as_dtype(spec)
        accum = np.promote_types(storage, np.float32)
        return cls(storage=storage, accum=accum)

    # -- application --------------------------------------------------------
    def storage_for(self, dtype) -> np.dtype:
        """The dtype an input of ``dtype`` is stored as under this policy."""
        return np.dtype(jnp.dtype(dtype)) if self.storage is None else self.storage

    def cast_storage(self, x):
        """Cast an array to the policy's storage dtype (no-op if None)."""
        return x if self.storage is None else x.astype(self.storage)

    def up(self, x):
        """Upcast into the accumulation dtype (compute happens here)."""
        return x.astype(self.accum)

    def down(self, x, like=None):
        """Downcast a computed value back to storage (or ``like``'s dtype)."""
        target = like.dtype if like is not None else self.storage
        return x if target is None else x.astype(target)

    def bytes_per_element(self, input_dtype) -> int:
        """Stored bytes per L element — the bandwidth-bound quantity."""
        return int(self.storage_for(input_dtype).itemsize)

    def __repr__(self):
        st = "input" if self.storage is None else str(self.storage)
        return f"Precision(storage={st}, accum={self.accum})"


# The legacy policy: no casts, compute wherever the inputs already are.
DEFAULT = None
