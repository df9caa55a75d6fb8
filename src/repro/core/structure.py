"""Storage-structure layer: the factor's layout as a first-class object
(DESIGN.md §12).

The source paper's headline scaling claim is O(n) GPU memory for the
modification path, yet a dense ``(n, n)`` factor caps everything at O(n²)
bytes before a single update runs. This module splits *what the factor is*
(an upper Cholesky factor of an SPD matrix) from *how it is laid out*:

* ``DenseStorage``        — the ``(n, n)`` / ``(B, n, n)`` array layout every
  existing backend consumes; behaviour-identical to the pre-refactor
  ``CholFactor`` code paths (same ops, same vmap structure, bit-for-bit).
* ``BlockTriDiagStorage`` — the factor of a block-tridiagonal SPD matrix
  (Kalman smoothing / MPC normal equations, Schwan et al. in PAPERS.md):
  an upper block-BIdiagonal factor stored as ``(nb, b, b)`` diagonal blocks
  plus ``(nb-1, b, b)`` coupling blocks — O(n·b) memory for n = nb·b.

``FactorStorage`` is the protocol ``CholFactor`` delegates every
layout-specific operation to: diagonal extraction, triangular solves,
``logdet``, validity, densification, scaling, dtype casts, and (via the
pytree registration) the checkpoint leaf layout. The factor object itself
is polymorphic over structure; its public API does not change.

Math convention (same as the rest of the repo): upper factor, ``A = U^T U``.
For a block-tridiagonal ``A`` with diagonal blocks ``Ad[j]`` and
super-diagonal blocks ``Ao[j] = A[j·b:(j+1)·b, (j+1)·b:(j+2)·b]`` (the
sub-diagonal blocks are their transposes), the factor is block bidiagonal:

    U[j, j]   = diag[j]   (upper triangular, positive diagonal)
    U[j, j+1] = off[j]    (dense b×b)

with the chain recurrence (Schwan et al., transposed to the upper
convention)::

    S_0     = Ad[0]
    diag[j] = chol_upper(S_j)
    off[j]  = diag[j]^{-T} Ao[j]
    S_{j+1} = Ad[j+1] - off[j]^T off[j]

Rank-k modification support: a modification ``A ± V V^T`` stays
block-tridiagonal — and the factor stays block-bidiagonal, i.e.
representable in this storage — iff every COLUMN of ``V`` is supported
inside one adjacent block-row pair ``{j, j+1}``. That is exactly the
update traffic of the structured workloads (a Kalman measurement touches
one state block; a dynamics term touches one adjacent pair). See
``assert_blocklocal`` for the host-side validator and
``repro.kernels.blocktridiag`` for the dependency argument.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import solve as _solve
from repro.core.precision import Precision


def _mm(a, b):
    """Matmul at fp32 contract precision (TPU's default rounds f32 operands
    to bf16 on the MXU)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _mT(x):
    """Matrix transpose over the trailing two axes (batched-safe)."""
    return jnp.swapaxes(x, -1, -2)


@runtime_checkable
class FactorStorage(Protocol):
    """What ``CholFactor`` requires of a storage layout.

    Implementations are frozen dataclasses registered as pytrees (their
    leaves ARE the checkpoint leaf layout) and carry:

    * ``structure`` — the registry key backends declare support for
      (``'dense'``, ``'blocktridiag'``, ...);
    * ``n`` / ``batched`` / ``dtype`` — metadata views;
    * ``diagonal / solve / solve_triangular / logdet / is_valid /
      downdate_feasible / matrix / to_dense / astype`` — the
      layout-specific operations;
    * ``raw`` — the value ``CholFactor.data`` holds (the bare array for
      dense — keeping the dense pytree/checkpoint layout bit-identical to
      the pre-refactor factor — and the storage object itself otherwise).
    """

    structure: str

    @property
    def n(self) -> int: ...

    @property
    def batched(self) -> bool: ...

    @property
    def dtype(self): ...

    @property
    def raw(self): ...

    def diagonal(self): ...

    def solve(self, b): ...

    def solve_triangular(self, b, *, trans: bool): ...

    def logdet(self): ...

    def is_valid(self, *, tol: float = 0.0): ...

    def downdate_feasible(self, V): ...

    def matrix(self): ...

    def to_dense(self): ...

    def astype(self, dtype): ...


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DenseStorage:
    """The ``(n, n)`` / ``(B, n, n)`` array layout (the pre-refactor one).

    Every method is the literal operation ``CholFactor`` used to inline —
    same solve calls, same vmap-over-leading-axis batching — so dense
    behaviour through the delegation is bit-identical.
    """

    data: jax.Array

    structure = "dense"

    def tree_flatten(self):
        return (self.data,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)

    # -- metadata views -----------------------------------------------------
    @property
    def n(self) -> int:
        return self.data.shape[-1]

    @property
    def batched(self) -> bool:
        return self.data.ndim == 3

    @property
    def batch(self):
        """Fleet size when batched, else None."""
        return self.data.shape[0] if self.data.ndim == 3 else None

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def raw(self):
        # CholFactor.data stays the bare array: the dense pytree leaf /
        # checkpoint layout predates the storage layer and must not change.
        return self.data

    # -- layout-specific operations -----------------------------------------
    def _percore(self, fn, *args):
        if self.batched:
            return jax.vmap(fn)(self.data, *args)
        return fn(self.data, *args)

    def diagonal(self):
        return jnp.diagonal(self.data, axis1=-2, axis2=-1)

    def solve(self, b):
        return self._percore(_solve.chol_solve, b)

    def solve_triangular(self, b, *, trans: bool):
        if self.batched:
            return jax.vmap(
                lambda L, rhs: _solve.solve_triangular(L, rhs, trans=trans)
            )(self.data, b)
        return _solve.solve_triangular(self.data, b, trans=trans)

    def logdet(self):
        return self._percore(_solve.chol_logdet)

    def is_valid(self, *, tol: float = 0.0):
        return self._percore(lambda L: _solve.is_positive_factor(L, tol=tol))

    def downdate_feasible(self, V):
        return self._percore(_solve.downdate_feasible, V)

    def matrix(self):
        return _mT(self.data) @ self.data

    def to_dense(self):
        return self.data

    def blocks_like(self, dense):
        # Tangent re-entry (autodiff.diffable_update_structured): dense is
        # already this storage's layout.
        return DenseStorage(dense.astype(self.dtype))

    def astype(self, dtype):
        return DenseStorage(self.data.astype(dtype))

    def describe(self) -> str:
        return "x".join(str(s) for s in self.data.shape)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockTriDiagStorage:
    """Upper block-bidiagonal factor of a block-tridiagonal SPD matrix.

    Attributes:
      diag: ``(nb, b, b)`` upper-triangular diagonal blocks ``U[j, j]``,
        or ``(B, nb, b, b)`` for a fleet of B factors.
      off:  ``(nb-1, b, b)`` coupling blocks ``U[j, j+1]`` (the transposes
        of the lower factor's sub-diagonal blocks), or ``(B, nb-1, b, b)``.

    O(n·b) memory for ``n = nb·b`` — the layout for factors whose dense
    ``(n, n)`` form would not fit. Batched (4-D leaves) storage is a fleet
    of factors over one shared chain layout: every per-factor operation
    vmaps over the leading axis, mirroring ``DenseStorage``'s ``(B, n, n)``
    convention so ``FactorStore`` can hold structured members.
    """

    diag: jax.Array
    off: jax.Array

    structure = "blocktridiag"

    def __post_init__(self):
        d, o = jnp.shape(self.diag), jnp.shape(self.off)
        if len(d) not in (3, 4) or d[-1] != d[-2]:
            raise ValueError(f"diag must be (nb, b, b) or (B, nb, b, b), "
                             f"got {d}")
        if (len(o) != len(d) or o[-2:] != d[-2:] or o[-3] != d[-3] - 1
                or o[:-3] != d[:-3]):
            raise ValueError(
                f"off must be (..., nb-1, b, b) matching diag {d}, got {o}")

    def tree_flatten(self):
        return (self.diag, self.off), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        diag, off = children
        # Bypass validation: transient pytree states (tracers in vjp/scan
        # internals, restore placeholders) may carry object() leaves.
        obj = object.__new__(cls)
        object.__setattr__(obj, "diag", diag)
        object.__setattr__(obj, "off", off)
        return obj

    # -- metadata views -----------------------------------------------------
    @property
    def nblocks(self) -> int:
        return self.diag.shape[-3]

    @property
    def block(self) -> int:
        return self.diag.shape[-1]

    @property
    def n(self) -> int:
        return self.nblocks * self.block

    @property
    def batched(self) -> bool:
        return self.diag.ndim == 4

    @property
    def batch(self):
        """Fleet size when batched, else None."""
        return self.diag.shape[0] if self.batched else None

    @property
    def dtype(self):
        return self.diag.dtype

    @property
    def raw(self):
        return self

    def _per(self, fn, *args):
        """vmap ``fn(unbatched_storage, *args)`` over the fleet axis."""
        if self.batched:
            return jax.vmap(
                lambda d, o, *a: fn(BlockTriDiagStorage(d, o), *a)
            )(self.diag, self.off, *args)
        return fn(self, *args)

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_matrix_blocks(cls, Ad, Ao) -> "BlockTriDiagStorage":
        """Factor block-tridiagonal SPD blocks (Schwan et al. chain).

        ``Ad``: (nb, b, b) diagonal blocks; ``Ao``: (nb-1, b, b)
        super-diagonal blocks ``A[j, j+1]``. O(nb·b³) work, O(n·b) memory —
        the structured analogue of ``CholFactor.from_matrix``.
        """
        Ad, Ao = jnp.asarray(Ad), jnp.asarray(Ao)

        def step(S, x):
            ao, ad_next = x
            U = _mT(jnp.linalg.cholesky(S))
            off = jax.scipy.linalg.solve_triangular(U, ao, trans=1,
                                                    lower=False)
            return ad_next - _mm(_mT(off), off), (U, off)

        S_last, (diag_head, off) = jax.lax.scan(step, Ad[0], (Ao, Ad[1:]))
        U_last = _mT(jnp.linalg.cholesky(S_last))
        return cls(jnp.concatenate([diag_head, U_last[None]], axis=0), off)

    @classmethod
    def from_dense(cls, L, block: int) -> "BlockTriDiagStorage":
        """Slice an (n, n) upper block-bidiagonal factor into blocks.

        Entries outside the two block diagonals are DROPPED (callers assert
        they are zero where that matters — see the conformance tests).
        """
        n = L.shape[-1]
        if n % block:
            raise ValueError(f"block {block} does not divide n={n}")
        nb = n // block
        diag = jnp.stack([L[j * block:(j + 1) * block,
                            j * block:(j + 1) * block] for j in range(nb)])
        if nb > 1:
            off = jnp.stack([L[j * block:(j + 1) * block,
                               (j + 1) * block:(j + 2) * block]
                             for j in range(nb - 1)])
        else:
            off = jnp.zeros((0, block, block), L.dtype)
        return cls(diag, off)

    @classmethod
    def identity(cls, nb: int, block: int, *, scale: float = 1.0,
                 dtype=jnp.float32, batch=None) -> "BlockTriDiagStorage":
        """Factor of ``scale * I`` in block form (the warm start). With
        ``batch=B`` the fleet variant: B identical members, 4-D leaves."""
        eye = jnp.sqrt(jnp.asarray(scale, dtype)) * jnp.eye(block, dtype=dtype)
        dshape = (nb, block, block)
        oshape = (max(nb - 1, 0), block, block)
        if batch is not None:
            dshape, oshape = (batch,) + dshape, (batch,) + oshape
        return cls(jnp.broadcast_to(eye, dshape), jnp.zeros(oshape, dtype))

    def blocks_like(self, dense) -> "BlockTriDiagStorage":
        """Extract this storage's block pattern from a dense (n, n) matrix,
        cast to this storage's leaf dtypes (the autodiff tangent re-entry
        point — see ``repro.core.autodiff.diffable_update_structured``)."""
        out = BlockTriDiagStorage.from_dense(dense, self.block)
        return BlockTriDiagStorage(out.diag.astype(self.diag.dtype),
                                   out.off.astype(self.off.dtype))

    # -- densification (diagnostics / tests / tangent lift only) ------------
    def to_dense(self):
        """The (n, n) / (B, n, n) upper factor — O(n²) memory, diagnostics
        only; the modification path never calls this (asserted via jaxpr
        inspection in tests/test_structure.py)."""
        if self.batched:
            return self._per(lambda s: s.to_dense())
        b, nb = self.block, self.nblocks
        out = jnp.zeros((self.n, self.n), self.dtype)
        for j in range(nb):
            out = jax.lax.dynamic_update_slice(out, self.diag[j],
                                               (j * b, j * b))
        for j in range(nb - 1):
            out = jax.lax.dynamic_update_slice(out, self.off[j],
                                               (j * b, (j + 1) * b))
        return out

    def matrix(self):
        """Materialise ``A = U^T U`` (O(n²) — diagnostics only)."""
        L = self.to_dense()
        return _mT(L) @ L

    def matrix_blocks(self):
        """``(Ad, Ao)`` of ``A = U^T U`` in block form — O(n·b), the
        structured counterpart of ``matrix()``."""
        ad = _mT(self.diag) @ self.diag
        if self.nblocks > 1:
            ad = ad.at[..., 1:, :, :].add(_mT(self.off) @ self.off)
        ao = _mT(self.diag[..., :-1, :, :]) @ self.off
        return ad, ao

    # -- layout-specific operations -----------------------------------------
    def diagonal(self):
        d = jnp.diagonal(self.diag, axis1=-2, axis2=-1)
        return d.reshape(d.shape[:-2] + (-1,))

    def _blocks_of(self, rhs):
        """(n, ...) -> (nb, b, ...) block view of a right-hand side."""
        if rhs.shape[0] != self.n:
            raise ValueError(
                f"rhs leading dim {rhs.shape[0]} != n={self.n}")
        return rhs.reshape((self.nblocks, self.block) + rhs.shape[1:])

    def solve_triangular(self, b, *, trans: bool):
        """``U^T x = b`` (trans) or ``U x = b`` by block substitution.

        Forward (trans): ``y_j = U_jj^{-T} (b_j - off_{j-1}^T y_{j-1})``.
        Backward:        ``x_j = U_jj^{-1} (y_j - off_j x_{j+1})``.
        One lax.scan over the block chain either way — O(nb·b²·m) work,
        never a dense (n, n) operand.
        """
        if self.batched:
            return self._per(
                lambda s, rhs: s.solve_triangular(rhs, trans=trans), b)
        b = jnp.asarray(b)
        bb = self._blocks_of(b)
        st = jax.scipy.linalg.solve_triangular
        if trans:
            y0 = st(self.diag[0], bb[0], trans=1, lower=False)

            def fwd(y_prev, x):
                U, R, rhs = x
                y = st(U, rhs - _mm(_mT(R), y_prev), trans=1, lower=False)
                return y, y

            _, tail = jax.lax.scan(fwd, y0, (self.diag[1:], self.off, bb[1:]))
            out = jnp.concatenate([y0[None], tail], axis=0)
        else:
            xl = st(self.diag[-1], bb[-1], trans=0, lower=False)

            def bwd(x_next, x):
                U, R, rhs = x
                xj = st(U, rhs - _mm(R, x_next), trans=0, lower=False)
                return xj, xj

            _, head = jax.lax.scan(bwd, xl,
                                   (self.diag[:-1], self.off, bb[:-1]),
                                   reverse=True)
            out = jnp.concatenate([head, xl[None]], axis=0)
        return out.reshape(b.shape)

    def solve(self, b):
        y = self.solve_triangular(b, trans=True)
        return self.solve_triangular(y, trans=False)

    def logdet(self):
        return 2.0 * jnp.sum(jnp.log(self.diagonal()), axis=-1)

    def is_valid(self, *, tol: float = 0.0):
        return jnp.all(self.diagonal() > tol, axis=-1)

    def downdate_feasible(self, V):
        """Same criterion as the dense path (``I - P^T P`` PD for
        ``U^T P = V``) — the forward substitution keeps it O(n·b·k).
        Batched storage takes (B, n, k) V and returns (B,) verdicts."""
        if self.batched:
            return self._per(lambda s, v: s.downdate_feasible(v), V)
        if V.ndim == 1:
            V = V[:, None]
        P = self.solve_triangular(V, trans=True)
        G = jnp.eye(V.shape[1], dtype=self.dtype) - _mm(P.T, P)
        return jnp.all(jnp.linalg.eigvalsh(G) > 0)

    def astype(self, dtype):
        return BlockTriDiagStorage(self.diag.astype(dtype),
                                   self.off.astype(dtype))

    def describe(self) -> str:
        if self.batched:
            return f"blocktridiag[{self.batch}x{self.nblocks}x{self.block}]"
        return f"blocktridiag[{self.nblocks}x{self.block}]"


#: Storage classes the layer knows about; ``as_storage`` wraps raw arrays
#: in DenseStorage and passes these through.
STORAGE_CLASSES = (DenseStorage, BlockTriDiagStorage)


def is_factor_storage(x) -> bool:
    """True for structured storage objects (raw arrays are dense data)."""
    return isinstance(x, STORAGE_CLASSES)


def as_storage(data) -> FactorStorage:
    """The delegation view of a ``CholFactor.data`` value."""
    if is_factor_storage(data):
        return data
    return DenseStorage(data)


def assert_blocklocal(V, block: int):
    """Host-side validator of the structured modification contract.

    Each column of ``V`` must be supported inside one adjacent block-row
    pair ``{j, j+1}`` for ``A ± V V^T`` to stay block-tridiagonal (anything
    wider generates fill-in the storage cannot represent). Traced values
    cannot be checked — call this from eager/test/ingest code, not inside
    jit.
    """
    import numpy as np

    V = np.asarray(V)
    if V.ndim == 1:
        V = V[:, None]
    for m in range(V.shape[1]):
        nz = np.nonzero(V[:, m])[0]
        if nz.size == 0:
            continue
        first, last = int(nz[0]) // block, int(nz[-1]) // block
        if last - first > 1:
            raise ValueError(
                f"column {m} of V spans block rows {first}..{last}; the "
                "block-tridiagonal modification contract allows one "
                "adjacent pair (A ± v v^T would leave the storage class)")


def anchor_block(v, block: int):
    """Anchor block-row of a block-local rank-1 row: the FIRST block row
    its support touches (a row supported on pair {j, j+1} anchors at j).

    Validates the block-local contract on the way (raises the same
    ``ValueError`` as ``assert_blocklocal``); returns ``None`` for an
    all-zero row, which is block-local trivially and anchors nowhere.
    Host-side only — the coalescer keys structured rows by this value at
    ``push()`` time so a contract violation fails at ingest, not inside
    the kernel.
    """
    import numpy as np

    v = np.asarray(v).reshape(-1)
    nz = np.nonzero(v)[0]
    if nz.size == 0:
        return None
    first, last = int(nz[0]) // block, int(nz[-1]) // block
    if last - first > 1:
        raise ValueError(
            f"column 0 of V spans block rows {first}..{last}; the "
            "block-tridiagonal modification contract allows one "
            "adjacent pair (A ± v v^T would leave the storage class)")
    return first


def chol_update_blocktridiag_ref(S, V, *, sigma: int = 1, precision=None,
                                 **_ignored):
    """Pure-jnp block-chain rank-k up/down-date — the lax.scan twin of the
    Pallas kernel (``repro.kernels.blocktridiag``), and the fast CPU path.

    Walks the block chain exactly like the dense blocked driver walks
    panels: the diagonal recurrence on block j annihilates the ``V^T`` slab
    of block j and emits the panel transform ``T``; the apply transforms
    the single trailing tile the structure has — ``off[j]`` — together with
    the next ``V^T`` slab, which carries the cascade to block j+1. All
    other trailing tiles are zero and all other slabs belong to columns
    whose rotations at this block are identities (the block-local support
    contract), so skipping them is exact, not approximate.

    O(k·b²·nb) work, O(n·(b+k)) memory; never materialises (n, n).
    """
    from repro.core import blocked

    if sigma not in (1, -1):
        raise ValueError(f"sigma must be +1 or -1, got {sigma}")
    precision = Precision.parse(precision)
    if precision is not None:
        S = precision.cast_storage(S)
        V = precision.cast_storage(V)
    up = (lambda x: x) if precision is None else precision.up
    if V.ndim == 1:
        V = V[:, None]
    nb, b = S.nblocks, S.block
    k = V.shape[1]
    store = S.dtype
    # (nb, k, b) V^T slabs + a zero tail slab / zero tail off-block so the
    # last chain step is a regular (zero-GEMM) apply.
    slabs = jnp.swapaxes(V.T.reshape(k, nb, b), 0, 1)
    slabs_next = jnp.concatenate(
        [slabs[1:], jnp.zeros((1, k, b), slabs.dtype)], axis=0)
    offp = jnp.concatenate(
        [S.off, jnp.zeros((1, b, b), S.off.dtype)], axis=0)

    def step(slab, xs):
        D, R, nxt = xs
        D_new, T = blocked.panel_diag(up(D), up(slab), sigma)
        R_new, nxt_new = blocked.panel_apply_gemm(up(R), up(nxt), T)
        return nxt_new.astype(store), (D_new.astype(store),
                                       R_new.astype(store))

    _, (diag_new, off_new) = jax.lax.scan(step, slabs[0],
                                          (S.diag, offp, slabs_next))
    return BlockTriDiagStorage(diag_new, off_new[:nb - 1])
