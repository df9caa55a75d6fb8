"""Batched rank-k modification of single-tile factors, members in the lanes.

A fleet of small factors (per-user regression, bandits: ``n`` of a few
dozen) fits in one panel each. Vmapping the fused kernel over such a fleet
pads every member to a ``panel x panel`` tile and walks the members one
grid step after another, each through a serial diagonal phase of
``panel`` rows, most of them padding (DESIGN.md §5.2). This kernel runs
the same block reflection per row (``cholupdate.diag_reflect``) on up to
128 members at once, one member per lane:

* the factors enter as ``(n, n, B)`` and the rows as ``(k, n, B)``, ``B``
  on the lanes — the layout XLA already keeps a ``(B, n, n)`` fleet in on
  the TPU, so the factors' transposes in the wrapper are bitcasts;
* the grid walks groups of 128 members (one group of all ``B`` when
  ``B < 128``); each group walks rows ``i = 0 .. n-1`` and no further;
* row ``i`` of every member at once, element-wise over the lanes::

      a = L[i,i],  v = V[:, i],  w = sqrt(a² + σ‖v‖²)
      L[i, j] <- (a·L[i, j] + σ·vᵀV[:, j]) / w     (j > i),  L[i, i] <- w
      V <- V − v ⊗ (L_old[i] + L_new[i]) / (a + w)

  There is no trailing panel, so no identity augmentation and no
  transform ``T``. Every row works on whole ``(n, G)`` slabs, masked by
  column, so every slice but the row's own ``V[:, i]`` is static and the
  row loop is one ``fori_loop``: the kernel body is traced once, which
  keeps the lowering of every program that holds it short (each serving
  process lowers its executables again before it finds them in the
  compile cache).

Precision (DESIGN.md §8): the factors move in the STORAGE dtype; the rows
are held, and every computation runs, in the ACCUMULATION dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import Precision
from repro.obs import metrics as _obs_metrics
from repro.obs import phases

#: Members per grid step: one per lane of a vector register.
LANES = 128
#: Largest member order the kernel takes. A group's factor blocks, in and
#: out and double-buffered, take 4·n²·128 words of VMEM: 34 MB at
#: n = 128, past the chip's VMEM (128 MiB on v5e) at n = 256.
MAX_ORDER = 128
#: Sublanes per (8, 128) tile of a 32-bit array.
_SUBLANES = 8


def kernel_name(sigma: int) -> str:
    """The stable name of the fleet kernel for one sign, as the profiler's
    trace shows it."""
    return "chol_fleet_update" if sigma > 0 else "chol_fleet_downdate"


def _fleet_kernel(l_ref, vt_ref, l_out, v_s, *, sigma, n, dt):
    """One group of members: rows ``0 .. n-1`` of each, members in lanes.

    ``l_ref``/``l_out``: (n, n, G) factors, row-major per member;
    ``vt_ref``: (k, n, G) rows; ``v_s``: their (k, n, G) working copy.
    Row ``i`` reads ``l_ref[i]`` and writes ``l_out[i]`` once each.
    """
    v_s[...] = vt_ref[...].astype(dt)
    j = jax.lax.broadcasted_iota(jnp.int32, (n, l_ref.shape[-1]), 0)

    def row(i, carry):
        r = l_ref[i].astype(dt)                          # (n, G)
        # L[i, i] by a masked sum: exact, and no dynamic sublane load
        # from a packed (bf16) factor.
        a = jnp.sum(jnp.where(j == i, r, 0), axis=0, keepdims=True)
        V = v_s[...]                                     # (k, n, G)
        v = v_s[:, pl.ds(i, 1), :]                       # (k, 1, G)
        q = jnp.sum(v * V, axis=0)                       # vᵀV
        w = jnp.sqrt(a * a + sigma * jnp.sum(v * v, axis=0))
        r_new = (a * r + sigma * q) * (1 / w)
        out = jnp.where(j > i, r_new, jnp.where(j == i, w, 0))
        l_out[i] = out.astype(l_out.dtype)
        c = jnp.where(j > i, (r + r_new) * (1 / (a + w)), 0)
        v_s[...] = V - v * c[None]
        return carry

    jax.lax.fori_loop(0, n, row, 0)


def _vmem_limit(n: int, k: int, group: int, itemsize: int, acc: int) -> int:
    """Scoped VMEM for one group: the factor blocks in and out and the row
    blocks, each double-buffered, the rows' working copy, and room for
    the row loop's temporaries."""
    rows = -(-n // _SUBLANES) * _SUBLANES
    lanes = -(-group // LANES) * LANES
    factor = n * rows * lanes * itemsize
    vrows = k * rows * lanes
    return 4 * factor + 2 * vrows * itemsize + vrows * acc + (16 << 20)


@functools.partial(jax.jit,
                   static_argnames=("sigma", "interpret", "accum_dtype"))
def _fleet_call(Lt, vt, *, sigma, interpret, accum_dtype=None):
    n, _, B = Lt.shape
    k = vt.shape[0]
    dt = jnp.dtype(accum_dtype or Lt.dtype)
    group = B if B < LANES else LANES
    _obs_metrics.counter("repro.kernels.launches", module="fleet",
                         lowering="mosaic").inc()
    with phases.scope(phases.KERNEL):
        return pl.pallas_call(
            functools.partial(_fleet_kernel, sigma=sigma, n=n, dt=dt),
            grid=(pl.cdiv(B, group),),
            in_specs=[pl.BlockSpec((n, n, group), lambda b: (0, 0, b)),
                      pl.BlockSpec((k, n, group), lambda b: (0, 0, b))],
            out_specs=pl.BlockSpec((n, n, group), lambda b: (0, 0, b)),
            out_shape=jax.ShapeDtypeStruct(Lt.shape, Lt.dtype),
            scratch_shapes=[pltpu.VMEM((k, n, group), dt)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_vmem_limit(n, k, group, Lt.dtype.itemsize,
                                             dt.itemsize)),
            interpret=interpret,
            name=kernel_name(sigma),
        )(Lt, vt)


def chol_update_fleet(L, V, *, sigma: int = 1, interpret=None,
                      precision=None):
    """Rank-k up/down-date of every member of a fleet of single-tile
    factors, in one ``pallas_call``.

    Args:
      L: (B, n, n) upper-triangular factors, ``A_b = L_b^T L_b``.
      V: (B, n, k) modification rows.
      sigma: +1 update, -1 downdate.
      interpret: force Pallas interpret mode (None: auto-detect, as for
        the fused kernel's Mosaic lowering).
      precision: storage/accum policy (``Precision``, 'bf16', or None).

    Returns:
      The (B, n, n) updated factors in the policy's storage dtype (``L``'s
      dtype when no policy is given). A downdate that leaves the positive
      definite cone gives non-finite entries, as on every path.
    """
    if sigma not in (1, -1):
        raise ValueError(f"sigma must be +1 or -1, got {sigma}")
    if L.ndim != 3 or L.shape[1] != L.shape[2]:
        raise ValueError(f"L must be (B, n, n), got shape {L.shape}")
    from repro.core.backends import default_interpret

    if interpret is None:
        interpret = default_interpret(lowering="mosaic")
    precision = Precision.parse(precision)
    accum_dtype = None
    if precision is not None:
        L = precision.cast_storage(L)
        V = precision.cast_storage(V)
        accum_dtype = jnp.dtype(precision.accum)
    with phases.scope(phases.PAD):
        Lt = jnp.transpose(L, (1, 2, 0))
        vt = jnp.transpose(V.astype(L.dtype), (2, 1, 0))
    out = _fleet_call(Lt, vt, sigma=sigma, interpret=bool(interpret),
                      accum_dtype=accum_dtype)
    with phases.scope(phases.UNPAD):
        return jnp.transpose(out, (2, 0, 1))
