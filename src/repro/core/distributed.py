"""Multi-device rank-k Cholesky modification (shard_map).

The paper streams O(n)-sized panels of ``L`` between host and GPU because the
factor does not fit device memory. At cluster scale the analogous regime is a
factor too large for one device, column-sharded over a mesh axis. The paper's
CPU/GPU split maps onto a device grid:

* the *diagonal phase* (serial, O(P^2 k)) is replicated on every device from a
  psum-gathered (P+k, P) stacked block — the analogue of the paper's
  host -> device upload of ``(c, s)`` (O(P k) there, O((P+k) P) here; one
  collective per panel);
* the *panel phase* is embarrassingly parallel over column shards, exactly as
  the paper's thread-per-column kernel: each device transforms the rows of its
  own columns.

Two strategies share that decomposition:

* ``fused`` (default) — the distributed fused composition (DESIGN.md §7):
  a jnp *chain phase* runs all diagonal recurrences and V^T evolution
  (one psum per panel, no kernels), then ONE Pallas launch per shard
  (``repro.kernels.sharded``) applies every off-diagonal tile. The key
  fact making the tiles independent: each row-panel of L is read in its
  original state (row-panels are written exactly once, by their own panel
  step), so all sequential coupling lives in the chain-phase outputs
  (``T^(p)``, ``D~^(p)``, and the running ``V^T`` snapshots).
* ``gemm`` — the per-panel jnp driver (transform GEMM) interleaved with
  the diagonal phase in one lax.scan, as in the original mapping (§4).

Finalized columns (global index < panel start) hold zeros in the active rows,
which every strategy maps to zeros, so devices do uniform-shape work with no
load imbalance; the triangular waste is accounted for in the §Perf analysis.

**Batched fleets (DESIGN.md §10).** ``L`` may be a stacked ``(B, n, n)``
fleet whose members are EACH column-sharded over the same mesh axis
(sharding spec ``P(None, None, axis)``): the serving-fleet composition for
per-user factors that outgrow one device. The chain phase vmaps over the
batch — which folds every per-panel psum-gather into ONE collective of a
``(B, P+k, P)`` stacked operand, not B collectives — and the fused panel
phase folds the batch into the grid of the SAME per-shard kernel, so a
whole fleet's rank-k update still costs exactly one Pallas launch per
shard: launches scale with shards (× sign blocks at the stream layer),
never with B.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import blocked
from repro.core.precision import Precision
from repro.runtime.compat import shard_map as _shard_map, shard_map_norep

AxisNames = Union[str, Sequence[str]]

STRATEGIES = ("fused", "gemm")


def axis_tuple(axis: AxisNames):
    """Canonical tuple form of a mesh-axis binding (str, tuple, or list).

    The one normalization every consumer shares — the sharded driver, the
    fleet placement and step-cache keys in ``repro.stream.store``."""
    return (axis,) if isinstance(axis, str) else tuple(axis)


_axis_tuple = axis_tuple  # internal alias (pre-existing call sites)


def _combined_axis_index(axes, mesh):
    """Linearised device index along possibly-multiple mesh axes."""
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def chol_update_sharded(
    L,
    V,
    *,
    sigma: int = 1,
    mesh,
    axis: AxisNames = "model",
    panel: int = 256,
    strategy: str = "fused",
    lowering: str = "auto",
    interpret: Optional[bool] = None,
    precision: Optional[Precision] = None,
):
    """Rank-k up/down-date of a column-sharded factor (or stacked fleet).

    Args:
      L: (n, n) upper factor, sharded ``P(None, axis)`` (or reshardable to
        it) — or a stacked fleet ``(B, n, n)``, each member column-sharded
        ``P(None, None, axis)``.
      V: (n, k) modification, replicated — ``(B, n, k)`` for a fleet.
      sigma: +1 / -1.
      mesh: the jax Mesh holding ``axis``.
      axis: mesh axis name (or tuple of names) the columns are sharded over.
      panel: row-panel size; must divide the per-device column count.
      strategy: 'fused' (one Pallas launch per shard, default) or 'gemm'
        (per-panel transform GEMM in jnp).
      lowering: per-shard kernel lowering for the fused strategy —
        'mosaic', 'portable', or 'auto' (resolve by device kind, see
        ``backends.resolve_lowering``). Ignored by the jnp strategies.
      interpret: Pallas interpret mode for the fused strategy (default:
        auto per the resolved lowering — the portable spec also compiles
        on GPU). An explicit value always wins. Ignored by the jnp
        strategies.
      precision: storage/accum policy (DESIGN.md §8). The shard tiles, the
        running V^T, and the per-panel psum-gathers move in the storage
        dtype (halving collective + HBM bytes under 'bf16'); the gathered
        diagonal blocks are cast to the accumulation dtype BEFORE the chain
        phase, so every replicated recurrence and transform stays fp32.

    Returns:
      The updated factor with the same sharding (storage dtype).
    """
    if sigma not in (1, -1):
        raise ValueError("sigma must be +1 or -1")
    if strategy not in STRATEGIES:
        raise ValueError(f"strategy must be one of {STRATEGIES}, got {strategy!r}")
    precision = Precision.parse(precision)
    if precision is not None:
        L = precision.cast_storage(L)
        V = precision.cast_storage(V)
    accum_dtype = None if precision is None else jnp.dtype(precision.accum)
    axes = _axis_tuple(axis)
    batched = L.ndim == 3
    n = L.shape[-1]
    if batched:
        if V.ndim == 2:
            V = V[:, :, None]
        if V.shape[:2] != (L.shape[0], n):
            raise ValueError(
                f"V must be (B, n, k) matching L {L.shape}, got {V.shape}")
        k = V.shape[-1]
    else:
        k = V.shape[1] if V.ndim == 2 else 1
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    if n % n_shards:
        raise ValueError(f"n={n} must divide over {n_shards} column shards")
    w_loc = n // n_shards
    if panel > w_loc or w_loc % panel:
        raise ValueError(
            f"panel={panel} must divide the per-device column count {w_loc}"
        )
    if n % panel:
        raise ValueError(f"n={n} must be a multiple of panel={panel}")
    from repro.core.backends import default_interpret, resolve_lowering

    lowering = resolve_lowering(lowering)
    if interpret is None:
        # Lowering-aware auto-detect (like the fused single-device kernel):
        # the mosaic per-shard spec compiles on TPU only; the portable spec
        # also on GPU. An explicit interpret= argument always wins.
        interpret = default_interpret(lowering=lowering)
    if batched:
        vt = jnp.swapaxes(V, -1, -2)  # (B, k, n)
        col_spec = P(None, None, axes)
    else:
        vt = jnp.reshape(V, (n, k)).T
        col_spec = P(None, axes)
    if strategy == "fused":
        fn = functools.partial(
            _sharded_update_fused, sigma=sigma, axes=axes, mesh=mesh,
            panel=panel, w_loc=w_loc, interpret=bool(interpret),
            accum_dtype=accum_dtype, lowering=lowering,
        )
        wrap = shard_map_norep  # pallas_call has no replication rule
    else:
        fn = functools.partial(
            _sharded_update_perpanel, sigma=sigma, axes=axes, mesh=mesh,
            panel=panel, w_loc=w_loc, accum_dtype=accum_dtype,
        )
        wrap = _shard_map
    mapped = wrap(
        fn,
        mesh=mesh,
        in_specs=(col_spec, col_spec),
        out_specs=col_spec,
    )
    L = jax.device_put(L, NamedSharding(mesh, col_spec))
    vt = jax.lax.with_sharding_constraint(vt, NamedSharding(mesh, col_spec))
    return mapped(L, vt)


def _gather_diag(L_loc, vt, p, *, panel, w_loc, me, axes):
    """psum-gather the stacked [D_p; V^T_d] block from its owner device."""
    k = vt.shape[0]
    r0 = p * panel
    owner = r0 // w_loc
    loc_r0 = r0 % w_loc
    d_cols = jax.lax.dynamic_slice(L_loc, (r0, loc_r0), (panel, panel))
    vtd = jax.lax.dynamic_slice(vt, (0, loc_r0), (k, panel))
    stacked = jnp.concatenate([d_cols, vtd], axis=0)
    stacked = jnp.where(owner == me, stacked, jnp.zeros_like(stacked))
    stacked = jax.lax.psum(stacked, axes)
    return stacked[:panel], stacked[panel:]


# ---------------------------------------------------------------------------
# Fused composition: chain phase (jnp) + one panel-phase kernel per shard.
# ---------------------------------------------------------------------------


def _chain_phase(L_loc, vt_loc, *, sigma, axes, panel, w_loc, me, gcol,
                 accum_dtype=None):
    """The chain phase for ONE factor's local shard (jnp, no kernels).

    Row-panels of L are never written here, so every slice below reads
    ORIGINAL factor data; the only sequential state is vt. Under
    ``jax.vmap`` (the batched fleet path) the per-panel psum-gather
    becomes a single collective over the stacked ``(B, P+k, P)`` operand —
    one gather per panel for the whole fleet, independent of B.
    """
    n = L_loc.shape[0]
    n_panels = n // panel
    acc_t = accum_dtype or jnp.float32

    def chain_body(vt, p):
        r0 = p * panel
        d_blk, vtd_g = _gather_diag(L_loc, vt, p, panel=panel, w_loc=w_loc,
                                    me=me, axes=axes)
        if accum_dtype is not None:
            # The psum gather moved storage-dtype bytes; the replicated
            # recurrence must NOT run there — upcast before the chain.
            d_blk = d_blk.astype(accum_dtype)
            vtd_g = vtd_g.astype(accum_dtype)
        D_new, T = blocked.panel_diag(d_blk, vtd_g, sigma)
        vt_in = vt  # snapshot entering panel p: the kernel's V^T operand
        R = jax.lax.dynamic_slice(L_loc, (r0, 0), (panel, w_loc))
        dot = functools.partial(jnp.dot, preferred_element_type=acc_t,
                                precision=jax.lax.Precision.HIGHEST)
        vt_new = (dot(T[panel:, :panel], R)
                  + dot(T[panel:, panel:], vt)).astype(vt.dtype)
        in_block = (gcol >= r0) & (gcol < r0 + panel)
        vt_new = jnp.where(in_block[None, :], jnp.zeros_like(vt_new), vt_new)
        return vt_new, (T, D_new, vt_in)

    _, stacks = jax.lax.scan(chain_body, vt_loc, jnp.arange(n_panels))
    return stacks  # (T_stack, D_stack, vt_stack)


def _sharded_update_fused(L_loc, vt_loc, *, sigma, axes, mesh, panel, w_loc,
                          interpret, accum_dtype=None, lowering="mosaic"):
    from repro.kernels import sharded as sharded_k

    me = _combined_axis_index(axes, mesh)
    gcol = me * w_loc + jnp.arange(w_loc)
    chain = functools.partial(
        _chain_phase, sigma=sigma, axes=axes, panel=panel, w_loc=w_loc,
        me=me, gcol=gcol, accum_dtype=accum_dtype,
    )
    if L_loc.ndim == 3:
        # Stacked fleet shard: vmap the chain (one psum per panel for the
        # whole batch), then fold B into the grid of the SAME launch.
        T_stack, D_stack, vt_stack = jax.vmap(chain)(L_loc, vt_loc)
    else:
        T_stack, D_stack, vt_stack = chain(L_loc, vt_loc)

    # --- panel phase: the whole update in ONE launch on this shard --------
    return sharded_k.panel_apply_sharded(
        L_loc, T_stack, D_stack, vt_stack,
        tile_off=me * (w_loc // panel), panel=panel, interpret=interpret,
        accum_dtype=accum_dtype, lowering=lowering,
    )


# ---------------------------------------------------------------------------
# Per-panel jnp strategy (the original §4 mapping).
# ---------------------------------------------------------------------------


def _sharded_update_perpanel(L_loc, vt_loc, *, sigma, axes, mesh, panel,
                             w_loc, accum_dtype=None):
    if L_loc.ndim == 3:
        # Stacked fleet shard: vmap the whole per-panel driver. The psum
        # inside batches into one collective per panel (jnp only — no
        # kernels to fold).
        return jax.vmap(functools.partial(
            _sharded_update_perpanel, sigma=sigma, axes=axes, mesh=mesh,
            panel=panel, w_loc=w_loc,
            accum_dtype=accum_dtype))(L_loc, vt_loc)
    n = L_loc.shape[0]
    me = _combined_axis_index(axes, mesh)
    dev_off = me * w_loc
    gcol = dev_off + jnp.arange(w_loc)
    n_panels = n // panel
    store = L_loc.dtype
    up = (lambda x: x) if accum_dtype is None else (
        lambda x: x.astype(accum_dtype))

    def panel_body(carry, p):
        L_loc, vt_loc = carry
        r0 = p * panel
        loc_r0 = r0 % w_loc
        # --- gather the stacked diagonal block to all devices (one psum) ---
        d_blk, vtd_g = _gather_diag(L_loc, vt_loc, p, panel=panel,
                                    w_loc=w_loc, me=me, axes=axes)
        # --- replicated serial diagonal phase (paper CPU role) — the psum
        # moved storage bytes; the recurrence itself runs in accum dtype ---
        d_new, T = blocked.panel_diag(up(d_blk), up(vtd_g), sigma)
        # --- parallel panel phase on local columns (paper GPU role) ---
        R = jax.lax.dynamic_slice(L_loc, (r0, 0), (panel, w_loc))
        R_new, vt_new = blocked.panel_apply_gemm(up(R), up(vt_loc), T)
        # --- stitch: inside-block columns take the serial result ---
        in_block = (gcol >= r0) & (gcol < r0 + panel)
        d_pad = jax.lax.dynamic_update_slice(
            jnp.zeros((panel, w_loc), d_new.dtype), d_new, (0, loc_r0)
        )
        R_final = jnp.where(in_block[None, :], d_pad, R_new).astype(store)
        vt_final = jnp.where(
            in_block[None, :], jnp.zeros_like(vt_new), vt_new
        ).astype(store)
        L_loc = jax.lax.dynamic_update_slice(L_loc, R_final, (r0, 0))
        return (L_loc, vt_final), None

    (L_loc, _), _ = jax.lax.scan(
        panel_body, (L_loc, vt_loc), jnp.arange(n_panels)
    )
    return L_loc
