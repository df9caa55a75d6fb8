"""Public API for rank-k Cholesky up/down-dating.

``chol_update`` is the single entry point the rest of the framework uses; the
``method`` argument names a backend from the registry
(``repro.core.backends``):

* ``reference``   — serial oracle (O(k n^2), paper Algorithm 1).
* ``gemm``        — panelled jnp driver, transform-matrix GEMM panel apply
                    (paper §4, TPU-native apply).
* ``fused``       — single-launch pipelined Pallas kernel: the whole panel
                    dependency chain in ONE ``pallas_call``, the panel
                    transform parked in VMEM scratch (DESIGN.md §5).
* ``sharded``     — column-sharded multi-device driver composing the fused
                    kernel, one launch per shard (DESIGN.md §7); pass
                    ``mesh=`` (and optionally ``axis=``).
* ``blocktridiag``/``blocktridiag_ref`` — the structured pair (DESIGN.md
                    §12): valid only for ``BlockTriDiagStorage`` factors,
                    as dense-only backends are valid only for arrays —
                    ``backends.methods(structure=...)`` reports the split.
* ``auto``        — heuristic (``backends.resolve``): fused on every
                    Pallas-capable device or under explicit interpret mode
                    (the Mosaic lowering on TPU, the portable one on GPU),
                    otherwise reference for tiny n and gemm beyond.

``precision`` is the storage/accum dtype policy (DESIGN.md §8): a
``repro.core.precision.Precision``, a preset string ('bf16', 'f32', ...),
or None (legacy: compute and store in the input dtype). Under 'bf16' the
L-tiles and the running ``V^T`` are stored in bfloat16 — halving the HBM
bytes of this bandwidth-bound problem — while the diagonal recurrence, the
panel transform ``T`` and all GEMM accumulation stay fp32. The
returned factor has the policy's storage dtype. Mixed-dtype inputs are
pinned: ``V`` is always cast to ``L``'s dtype before dispatch, on every
backend (no silent promotion of the factor).

Every path is differentiable: dispatch runs through the Murray (2016)
derivative rules in ``repro.core.autodiff`` (tangents/cotangents computed
in fp32 regardless of storage dtype), so ``jax.grad``/``jax.jvp`` of a
maintained factor never trace the underlying recurrence or kernel.

``chol_update_batched`` / ``chol_downdate_batched`` vmap any single-device
backend over stacked ``(B, n, n)`` factors — the serving workload of many
concurrent per-user updates. Both default to ``method='auto'`` and resolve
the heuristic ONCE per batch (same funnel as the single-factor path).
Two cases take the stacked fleet whole instead: ``method='sharded'``,
whose distributed path column-shards each member over the mesh axis and
folds the batch into the one-per-shard kernel launch (DESIGN.md §10), and
``fused`` members of one panel under the Mosaic lowering, which run as
one fleet kernel with the members in the lanes (DESIGN.md §5.2).

The stateful-factor object API (update/downdate/solve/logdet on one carried
value) lives in ``repro.core.factor.CholFactor``; these functions remain as
the thin functional face over the same registry.
"""
from __future__ import annotations

import collections
import threading
from typing import Optional

import jax

from repro.core import autodiff, backends
from repro.core import structure as _structure
from repro.core.precision import Precision

# ---------------------------------------------------------------------------
# Impl cache. One impl closure per (method, panel, interpret, precision,
# opts) so the custom_jvp wrapper sees a stable hashable callable (warm jit
# caches). Two leak hazards are handled here:
#
# * the cache is BOUNDED (LRU): a long-lived serving process that cycles
#   through many configurations must not retain every closure forever;
# * mesh-valued opts are keyed by identity-safe METADATA (axis names, shape,
#   device ids) rather than the Mesh object itself, so two equal meshes
#   built at different times share one entry instead of each pinning a
#   distinct closure (and its jit cache) — the old unbounded lru_cache
#   keyed on the raw object retained every mesh ever passed.
# ---------------------------------------------------------------------------

_IMPL_CACHE_MAX = 64
_impl_cache: "collections.OrderedDict" = collections.OrderedDict()
_impl_lock = threading.Lock()


def _opt_key(value):
    """A hashable, identity-safe cache key for one backend option value."""
    if hasattr(value, "axis_names") and hasattr(value, "devices"):
        # Mesh-like: key by what determines the computation, not object id.
        devs = tuple(id(d) for d in value.devices.flat)
        return ("mesh", tuple(value.axis_names),
                tuple(value.shape[a] for a in value.axis_names), devs)
    return value


def _cached_impl(method: str, panel: int, interpret: Optional[bool],
                 precision: Optional[Precision], opts: dict):
    key = (method, panel, interpret, precision,
           tuple((k, _opt_key(v)) for k, v in sorted(opts.items())))
    # Get-or-create under ONE lock hold: two threads racing the same first
    # call must receive the SAME closure (a per-thread duplicate would
    # defeat the stable-callable contract and double-trace under jit).
    with _impl_lock:
        impl = _impl_cache.get(key)
        if impl is not None:
            _impl_cache.move_to_end(key)
            return impl

        def impl(L, V, sigma):
            return backends.dispatch(L, V, sigma=sigma, method=method,
                                     panel=panel, interpret=interpret,
                                     precision=precision, **opts)

        _impl_cache[key] = impl
        while len(_impl_cache) > _IMPL_CACHE_MAX:
            _impl_cache.popitem(last=False)
        return impl


def impl_cache_len() -> int:
    """Current impl-cache size (bounded by ``_IMPL_CACHE_MAX``); for tests."""
    return len(_impl_cache)


def chol_update(
    L,
    V,
    *,
    sigma: int = 1,
    method: str = "auto",
    panel: int = 256,
    interpret: Optional[bool] = None,
    precision=None,
    **opts,
):
    """Rank-k up/down-date of the upper Cholesky factor L (A = L^T L).

    Args:
      L: (n, n) upper-triangular factor with positive diagonal.
      V: (n, k) or (n,) modification matrix; cast to ``L.dtype`` if it
        differs (the factor's dtype is never silently promoted).
      sigma: +1 for update (A + V V^T), -1 for downdate (A - V V^T).
      method: backend name or 'auto', see module docstring.
      panel: row-panel size for the blocked paths.
      interpret: force Pallas interpret mode (defaults to auto-detect per
        lowering: the fused kernel's mosaic lowering compiles on TPU only
        and its portable lowering on TPU and GPU — see
        ``backends.default_interpret``). An explicit value, including
        ``False``, always wins over the auto-detect.
      precision: storage/accum dtype policy ('bf16', a ``Precision``, or
        None = legacy single-dtype behaviour). The result carries the
        storage dtype.
      **opts: backend-specific options (e.g. ``mesh=``/``axis=`` for
        'sharded', ``lowering=`` for 'fused').

    Returns:
      The modified upper-triangular factor.
    """
    if method not in backends.methods():
        raise ValueError(
            f"method must be one of {backends.methods()}, got {method!r}"
        )
    if sigma not in (1, -1):
        raise ValueError(f"sigma must be +1 or -1, got {sigma}")
    structured = _structure.is_factor_storage(L)
    if structured and L.batched:
        raise ValueError(
            "batched structured storage goes through chol_update_batched "
            f"(got {L.describe()})"
        )
    if (not structured and L.ndim == 3 and method != "sharded"
            and not _fleet_native(method, L.shape[-1], panel, opts)):
        # Only the sharded backend (it folds the batch into its per-shard
        # launch) and the fused fleet kernel consume a stacked fleet
        # natively; every other backend batches through the vmapping
        # wrapper.
        raise ValueError(
            "stacked (B, n, n) factors go through chol_update_batched "
            f"(method={method!r})"
        )
    if V.ndim == 1:
        V = V[:, None]
    if V.dtype != L.dtype:
        # Pinned mixed-dtype behaviour (tests/test_factor.py): the factor's
        # dtype wins on every backend; no implicit jnp promotion of L.
        V = V.astype(L.dtype)
    precision = Precision.parse(precision)
    impl = _cached_impl(method, panel, interpret, precision, opts)
    if structured:
        # Structured storage carries its own Murray rule (the tangent is
        # re-extracted into the storage's block layout).
        return autodiff.diffable_update_structured(impl, sigma, L, V)
    return autodiff.diffable_update(impl, sigma, L, V)


def chol_update_batched(
    L,
    V,
    *,
    sigma: int = 1,
    method: str = "auto",
    panel: int = 256,
    interpret: Optional[bool] = None,
    precision=None,
    **opts,
):
    """Batched rank-k up/down-date over stacked factors (one vmapped launch).

    The serving workload: many concurrent per-user factors receive their own
    modification in one dispatch (e.g. a fleet of online-ridge windows, one
    per user). For the ``fused`` method B updates cost a single device
    launch: members of one panel run in the lanes of the fleet kernel,
    larger ones as one vmapped chain each, the batch folded into the grid.

    ``method`` defaults to ``'auto'`` — the SAME heuristic as the
    single-factor path — and is resolved once here for the whole batch, so
    the batched serving path can no longer silently bypass the device-kind
    routing (the old hard default of 'fused' did).

    Args:
      L: (B, n, n) stacked upper-triangular factors.
      V: (B, n, k) — or (B, n), broadcast to rank 1 — stacked modifications.
      sigma, method, panel, interpret, precision, **opts: as in
        ``chol_update`` (shared across the batch; per-element sigma would
        break the single-kernel grid).

    Returns:
      (B, n, n) stacked updated factors.
    """
    if _structure.is_factor_storage(L):
        # A structured FLEET: batched storage leaves, (B, n, k) rows. The
        # method resolves once against the storage's structure (same funnel
        # as the dense batch), then vmap maps the member rule over the
        # storage pytree — for the Pallas block-chain kernel the batch
        # folds into the grid, so B updates still construct ONE
        # pallas_call per sign block.
        if not L.batched:
            raise ValueError(
                f"structured fleet must be batched storage, got "
                f"{L.describe()}"
            )
        import jax.numpy as jnp

        V = jnp.asarray(V)
        if V.ndim == 2:
            V = V[:, :, None]
        if V.ndim != 3 or V.shape[0] != L.batch or V.shape[1] != L.n:
            raise ValueError(
                f"V must be (B, n, k) matching fleet {L.describe()}, got "
                f"{V.shape}"
            )
        method = backends.resolve(method, n=L.n, panel=panel,
                                  interpret=interpret, structure=L.structure)

        def one_s(l, v):
            return chol_update(
                l, v, sigma=sigma, method=method, panel=panel,
                interpret=interpret, precision=precision, **opts,
            )

        return jax.vmap(one_s)(L, V)
    if L.ndim != 3:
        raise ValueError(f"L must be (B, n, n), got shape {L.shape}")
    if V.ndim == 2:
        V = V[:, :, None]
    if V.ndim != 3 or V.shape[0] != L.shape[0] or V.shape[1] != L.shape[1]:
        raise ValueError(
            f"V must be (B, n, k) matching L {L.shape}, got {V.shape}"
        )
    if method == "sharded":
        # The sharded driver consumes the stacked fleet natively (chain
        # phase vmapped — one psum-gather per panel for the whole batch —
        # and B folded into the per-shard kernel grid), so it must NOT be
        # vmapped here: launches scale with shards, never with B.
        return chol_update(
            L, V, sigma=sigma, method="sharded", panel=panel,
            interpret=interpret, precision=precision, **opts,
        )
    # Resolve the heuristic ONCE for the batch (not per vmapped element).
    method = backends.resolve(method, n=L.shape[-1], panel=panel,
                              interpret=interpret)
    if _fleet_native(method, L.shape[-1], panel, opts):
        # Single-tile members: the fused backend takes the stacked fleet
        # whole, members in the lanes (``repro.kernels.fleet``), instead
        # of one padded chain per member under vmap.
        return chol_update(
            L, V, sigma=sigma, method=method, panel=panel,
            interpret=interpret, precision=precision, **opts,
        )

    def one(l, v):
        return chol_update(
            l, v, sigma=sigma, method=method, panel=panel, interpret=interpret,
            precision=precision, **opts,
        )

    return jax.vmap(one)(L, V)


def _fleet_native(method: str, n: int, panel: int, opts: dict) -> bool:
    """Whether a dense batched modification runs as ONE fleet kernel.

    Observed, not chosen: the fused backend under its Mosaic lowering
    (compiled or interpreted), members of at most one panel and at most
    ``fleet.MAX_ORDER``, and no mesh (a meshed fleet resolves to
    'sharded' and never gets here). Larger members keep the vmapped
    multi-tile chain (DESIGN.md §5.2).
    """
    from repro.kernels.fleet import MAX_ORDER

    return (method == "fused" and n <= min(panel, MAX_ORDER)
            and opts.get("mesh") is None
            and backends.resolve_lowering(opts.get("lowering")) == "mosaic")


def chol_downdate(L, V, **kw):
    """Convenience wrapper for ``chol_update(..., sigma=-1)``."""
    return chol_update(L, V, sigma=-1, **kw)


def chol_downdate_batched(L, V, **kw):
    """Convenience wrapper for ``chol_update_batched(..., sigma=-1)``."""
    return chol_update_batched(L, V, sigma=-1, **kw)
