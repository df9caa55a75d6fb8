"""``CholFactor``: the maintained Cholesky factor as a stateful pytree.

The paper's whole point is that a factor absorbs rank-k modifications
without refactorization — i.e. it is a *long-lived production object*, not
the return value of a one-shot routine. This module gives that object a
type: the upper factor plus its execution metadata (panel size, backend
name, dtype policy, interpret flag), with methods for every operation the
factor exists to serve::

    f = CholFactor.from_matrix(A, backend="auto")
    f = f.update(V)                  # A + V V^T, no refactorization
    f = f.downdate(V)                # A - V V^T, ditto
    x = f.solve(b)                   # two triangular solves
    ld = f.logdet()                  # 2 sum log diag
    ok = f.downdate_feasible(V)      # PD guard before a risky downdate

``CholFactor`` is a registered pytree: it jits, vmaps, scans, and lives
inside optimizer state (``repro.optim.cholesky_precond`` maintains one per
parameter). The array leaf is ``data``; everything else is static aux, so a
factor with a different backend is a different jaxpr — exactly the caching
behaviour you want.

Batching: ``data`` may be ``(B, n, n)`` — a fleet of per-user factors. All
methods vmap over the leading axis automatically, and updates still cost
one device launch on the fused backend (vmap folds B into the kernel grid).
Batching composes with sharding (DESIGN.md §10): a batched factor bound to
a mesh (``backend='sharded'``, ``mesh=``, ``axis=``) holds a fleet whose
members are EACH column-sharded ``P(None, None, axis)`` — factors too big
for one device — and mutations still cost ONE kernel launch per shard for
the whole fleet (the batch folds into the per-shard grid).

Every mutation dispatches through the backend registry
(``repro.core.backends``) wrapped in the Murray derivative rules
(``repro.core.autodiff``), so ``jax.grad`` through ``update``/``downdate``
works on every backend, including the Pallas kernels.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import api, backends
from repro.core import structure as _structure
from repro.core.precision import Precision
from repro.obs import phases

Axis = Union[str, tuple]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CholFactor:
    """Upper Cholesky factor (``A = L^T L``) + execution metadata.

    Attributes:
      data: (n, n) — or (B, n, n) batched — upper-triangular factor(s), OR
        a structured ``FactorStorage`` (e.g. ``BlockTriDiagStorage`` —
        ``CholFactor.from_blocktridiag``). Layout-specific operations are
        delegated to the storage layer (``repro.core.structure``,
        DESIGN.md §12); for dense data the delegate inlines the exact code
        this class used to carry, so dense behaviour is bit-identical and
        the pytree leaf stays the bare array.
      panel: row-panel size for the blocked/kernel backends.
      backend: registry name or 'auto' (resolved per call by heuristics).
      interpret: force Pallas interpret mode (None = auto-detect).
      precision: storage/accum dtype policy (``Precision``, a preset string
        like 'bf16', or None = compute and store in the factor's own dtype).
        Replaces the old scalar ``compute_dtype`` hook: 'bf16' stores L-tiles
        and the running V^T in bfloat16 while the diagonal recurrence,
        the panel transform and GEMM accumulation stay fp32 (DESIGN.md §8).
      mesh, axis: mesh binding for the 'sharded' backend (None otherwise).
        Valid for both single ``(n, n)`` and batched ``(B, n, n)`` data —
        the batched-sharded composition routes through the fleet-native
        distributed driver.
      lowering: fused-kernel lowering for the 'fused'/'sharded' backends —
        'mosaic', 'portable', or None/'auto' (resolve per device kind,
        DESIGN.md §5). Ignored by the jnp backends.
    """

    data: jax.Array
    panel: int = 256
    backend: str = "auto"
    interpret: Optional[bool] = None
    precision: Optional[Precision] = None
    mesh: Optional[object] = None
    axis: Axis = "model"
    lowering: Optional[str] = None

    def __post_init__(self):
        # Canonicalise string/dtype specs once, so the static aux is a
        # hashable Precision (or None) and equal policies compare equal.
        object.__setattr__(self, "precision", Precision.parse(self.precision))

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        aux = (self.panel, self.backend, self.interpret, self.precision,
               self.mesh, self.axis, self.lowering)
        return (self.data,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (data,) = children
        return cls(data, *aux)

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_matrix(cls, A, **meta) -> "CholFactor":
        """Factor an SPD matrix (O(n^3), once) into a maintained factor."""
        L = jnp.linalg.cholesky(A)
        return cls(jnp.swapaxes(L, -1, -2), **meta)

    @classmethod
    def from_factor(cls, L, **meta) -> "CholFactor":
        """Wrap an existing upper factor (no validation, no copy)."""
        if _structure.is_factor_storage(L):
            return cls(L, **meta)
        return cls(jnp.asarray(L), **meta)

    @classmethod
    def from_storage(cls, storage, **meta) -> "CholFactor":
        """Wrap a ``FactorStorage`` (dense storage unwraps to the array)."""
        return cls(storage.raw, **meta)

    @classmethod
    def from_blocktridiag(cls, Ad, Ao, **meta) -> "CholFactor":
        """Factor a block-tridiagonal SPD matrix given as blocks.

        ``Ad``: (nb, b, b) diagonal blocks; ``Ao``: (nb-1, b, b)
        super-diagonal blocks ``A[j, j+1]``. O(nb·b³) work, O(n·b) memory —
        the dense ``(n, n)`` matrix is never formed.
        """
        return cls(_structure.BlockTriDiagStorage.from_matrix_blocks(Ad, Ao),
                   **meta)

    @classmethod
    def identity(cls, n: int, *, scale: float = 1.0, batch: Optional[int] = None,
                 dtype=jnp.float32, **meta) -> "CholFactor":
        """Factor of ``scale * I`` — the canonical warm-start (eps*I stats)."""
        eye = jnp.sqrt(jnp.asarray(scale, dtype)) * jnp.eye(n, dtype=dtype)
        if batch is not None:
            eye = jnp.broadcast_to(eye, (batch, n, n))
        return cls(eye, **meta)

    # -- metadata views -----------------------------------------------------
    @property
    def storage(self) -> "_structure.FactorStorage":
        """The layout delegate (a zero-copy view; dense data gets wrapped)."""
        return _structure.as_storage(self.data)

    @property
    def structure(self) -> str:
        """'dense' or a structured layout name ('blocktridiag', ...)."""
        return getattr(self.data, "structure", "dense")

    @property
    def n(self) -> int:
        return self.storage.n

    @property
    def batched(self) -> bool:
        return self.storage.batched

    @property
    def dtype(self):
        return self.data.dtype

    def with_backend(self, backend: str, **meta) -> "CholFactor":
        """Same factor, different execution metadata (data is shared)."""
        return dataclasses.replace(self, backend=backend, **meta)

    def replace(self, **changes) -> "CholFactor":
        return dataclasses.replace(self, **changes)

    # -- the paper's operations --------------------------------------------
    def _mutate(self, V, sigma: int) -> "CholFactor":
        opts = {}
        if self.backend == "sharded":
            if self.mesh is None:
                raise ValueError("sharded backend requires a mesh binding "
                                 "(CholFactor(..., mesh=, axis=))")
            opts = {"mesh": self.mesh, "axis": self.axis}
        if self.lowering is not None and self.backend in (
                "auto", "fused", "sharded"):
            # Only the fused-kernel family understands the opt; 'auto' may
            # resolve to a jnp backend, which ignores extra opts by design.
            opts["lowering"] = self.lowering
        if self.batched:
            new = api.chol_update_batched(
                self.data, V, sigma=sigma, method=self.backend,
                panel=self.panel, interpret=self.interpret,
                precision=self.precision, **opts)
        else:
            new = api.chol_update(
                self.data, V, sigma=sigma, method=self.backend,
                panel=self.panel, interpret=self.interpret,
                precision=self.precision, **opts)
        return dataclasses.replace(self, data=new)

    def update(self, V) -> "CholFactor":
        """Absorb ``+ V V^T`` (rank k) without refactorization."""
        return self._mutate(V, 1)

    def downdate(self, V) -> "CholFactor":
        """Remove ``- V V^T`` (rank k) without refactorization."""
        return self._mutate(V, -1)

    def downdate_guarded(self, V):
        """Feasibility-guarded downdate: ``(factor', ok)``.

        ``factor'`` is the downdated factor where ``A - V V^T`` stays PD and
        the *unchanged* factor where it does not (``ok`` reports which).
        Both branches are computed (jnp.where semantics) — this is the jit-
        and vmap-safe guard for serving-time downdates of untrusted data.

        On the sharded backend the verdict comes from the downdated
        factor's diagonal (already psum-gathered and replicated by the
        chain phase) instead of ``downdate_feasible``'s triangular-solve
        criterion: the solve reads full rows, which a column-sharded
        layout would have to all-gather per guard, and the old
        ``ok[..., None, None]`` masking silently assumed those full rows
        were local. The recurrence leaves a non-positive or non-finite
        diagonal exactly when ``A - V V^T`` exits the PD cone, so the
        diagonal IS the feasibility verdict — at zero extra collectives.

        Everything the guard adds to the downdate runs under the
        ``repro.guard`` phase scope (``repro.obs.phases``).
        """
        down = self.downdate(V)
        with phases.scope(phases.GUARD):
            if self.structure != "dense":
                # Structured storage is a pytree of block arrays; the
                # verdict gates every leaf — scalar for one factor, (B,)
                # broadcast over each leaf's trailing block axes for a fleet.
                ok = self.downdate_feasible(V)

                def pick(d, o):
                    mask = ok.reshape(ok.shape + (1,) * (d.ndim - ok.ndim))
                    return jnp.where(mask, d, o)

                new = jax.tree.map(pick, down.data, self.data)
                return dataclasses.replace(self, data=new), ok
            if self.backend == "sharded":
                diag = jnp.diagonal(down.data, axis1=-2, axis2=-1)
                ok = jnp.all(jnp.isfinite(diag) & (diag > 0), axis=-1)
            else:
                ok = self.downdate_feasible(V)
            mask = ok[..., None, None] if self.batched else ok
            new = jnp.where(mask, down.data, self.data)
        return dataclasses.replace(self, data=new), ok

    def scale(self, alpha) -> "CholFactor":
        """Factor of ``alpha^2 * A``: exact exponential decay of statistics.

        Only ``|alpha|`` matters (the factor represents ``alpha^2 A``), so
        the magnitude is used: a raw negative multiplier would flip the
        diagonal sign and silently break the positive-diagonal invariant
        that ``is_valid``/``logdet``/``solve`` all rely on.
        """
        if self.structure != "dense":
            # Every block of the factor scales uniformly (U and its
            # coupling blocks alike), same as every dense entry.
            new = jax.tree.map(lambda x: x * jnp.abs(alpha), self.data)
            return dataclasses.replace(self, data=new)
        return dataclasses.replace(self, data=self.data * jnp.abs(alpha))

    # -- consumer operations (the reason the factor is maintained) ----------
    # All layout-specific: delegated to the storage (repro.core.structure).
    # Dense delegation inlines the literal old code paths (same solve calls,
    # same vmap batching) — bit-identical by construction.

    def solve(self, b):
        """Solve ``A x = b`` against the maintained factor."""
        return self.storage.solve(b)

    def solve_triangular(self, b, *, trans: bool):
        """One triangular solve: ``L^T x = b`` (trans) or ``L x = b``."""
        return self.storage.solve_triangular(b, trans=trans)

    def logdet(self):
        """``log det A`` from the maintained diagonal."""
        return self.storage.logdet()

    def downdate_feasible(self, V):
        """True where ``A - V V^T`` stays PD (per batch element)."""
        return self.storage.downdate_feasible(V)

    def is_valid(self, *, tol: float = 0.0):
        """Strictly positive diagonal — the factor invariant."""
        return self.storage.is_valid(tol=tol)

    def diagonal(self):
        """The factor's diagonal (sqrt of A's pivots), any layout."""
        return self.storage.diagonal()

    def matrix(self):
        """Materialise ``A = L^T L`` (O(n^3) — diagnostics only)."""
        return self.storage.matrix()

    def __repr__(self):  # keep aux readable in optimizer-state dumps
        return (f"CholFactor({self.storage.describe()} {self.dtype}, "
                f"panel={self.panel}, backend={self.backend!r})")


def resolve_backend_for(factor: CholFactor) -> str:
    """The concrete backend a factor's next mutation will run on."""
    return backends.resolve(factor.backend, n=factor.n, panel=factor.panel,
                            interpret=factor.interpret,
                            structure=factor.structure)
