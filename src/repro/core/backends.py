"""Backend registry for rank-k Cholesky up/down-dating (DESIGN.md §7).

Every execution path of the modification — the serial oracle, the panelled
jnp driver, the single-launch fused kernel, the block-chain kernel and its
jnp twin, and the column-sharded multi-device driver — is a registered
implementation of ONE protocol::

    update(L, V, *, sigma, panel, interpret, **opts) -> L_new

``repro.core.api.chol_update`` dispatches through this table instead of an
if/elif ladder, and ``resolve`` replaces hard-coded method strings with a
heuristic over (device kind, problem size, interpret mode), so consumers ask
for *a* backend ("auto") rather than *the* backend.

Registration is eager (the names exist at import time) but the Pallas and
distributed modules are imported lazily inside each backend function, so the
pure-JAX core carries no kernel dependencies until a kernel path runs.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.core.precision import Precision
from repro.obs import metrics as obs_metrics

# Device kinds Pallas can lower kernels for: TPU (Mosaic) and GPU (Triton).
# The paper's target hardware is the GPU — 'auto' routing must not treat
# TPU as the only kernel-capable device. The fused kernel has TWO lowerings
# of one kernel body (DESIGN.md §5): the Mosaic spec (scalar-prefetch index
# table + pltpu.VMEM scratch) where it wins, and a portable spec (plain
# pl.GridSpec, chain-walk state in loop carries) that Triton can compile —
# so GPU kinds take the single-launch path too.
PALLAS_DEVICE_KINDS = ("tpu", "gpu", "cuda", "rocm")
MOSAIC_DEVICE_KINDS = ("tpu",)
PORTABLE_DEVICE_KINDS = ("gpu", "cuda", "rocm")

#: Valid ``lowering=`` values for the fused kernel family ('auto' and None
#: both mean "resolve by device kind").
LOWERINGS = ("auto", "mosaic", "portable")

# Environment overrides, used by the CI routing job (test-gpu-routing):
# REPRO_FAKE_DEVICE_KIND makes every routing heuristic see a chosen device
# kind without real hardware; REPRO_FORCE_INTERPRET=1 pins the interpret
# auto-detect to True so kernels selected for that fake kind still execute
# (in interpret mode) on the host actually running the suite. Explicit
# ``interpret=`` arguments are never touched by either.
FAKE_DEVICE_KIND_ENV = "REPRO_FAKE_DEVICE_KIND"
FORCE_INTERPRET_ENV = "REPRO_FORCE_INTERPRET"


def device_kind() -> str:
    """The device kind every routing heuristic keys on (lowercase).

    Reads ``REPRO_FAKE_DEVICE_KIND`` first so a whole test run can exercise
    the GPU routing path from a CPU host, then falls back to the real
    ``jax.default_backend()``.
    """
    fake = os.environ.get(FAKE_DEVICE_KIND_ENV)
    if fake:
        return fake.lower()
    return jax.default_backend().lower()


_current_device_kind = device_kind  # alias: params named device_kind shadow


def resolve_lowering(lowering: Optional[str] = None, *,
                     device_kind: Optional[str] = None) -> str:
    """Map a ``lowering`` request (possibly None/'auto') to a concrete one.

    'mosaic' keeps the PrefetchScalarGridSpec + pltpu.VMEM scratch spec —
    the tuned TPU path (and the interpret-mode default off-GPU). 'portable'
    is the plain-GridSpec spec whose chain-walk state lives in loop carries,
    which Triton can lower — the auto choice on gpu/cuda/rocm kinds.
    """
    if lowering in ("mosaic", "portable"):
        return lowering
    if lowering not in (None, "auto"):
        raise ValueError(
            f"lowering must be one of {LOWERINGS}, got {lowering!r}")
    kind = (device_kind or _current_device_kind()).lower()
    return "portable" if kind in PORTABLE_DEVICE_KINDS else "mosaic"


def default_interpret(*, lowering: Optional[str] = None) -> bool:
    """Interpret-mode auto-detect, shared by every kernel entry point.

    Callers pass this ONLY when no explicit ``interpret=`` argument was
    given — an explicit argument (including ``False``) always wins over
    this heuristic (see tests/test_fused.py's regression).

    ``lowering`` selects the fused-kernel policy: the 'mosaic' lowering
    compiles on TPU only; the 'portable' lowering also compiles on GPU via
    Triton (so GPU kinds no longer hard-force interpret mode for the fused
    kernel). Without it, any Pallas-capable kind (TPU or GPU) counts: the
    block-chain kernel's one lowering.

    ``REPRO_FORCE_INTERPRET=1`` pins the result to True (the CI fake-GPU
    routing job: routing resolves for 'gpu', execution stays interpretable
    on the CPU host actually running it).
    """
    if os.environ.get(FORCE_INTERPRET_ENV, "") not in ("", "0"):
        return True
    kind = device_kind()
    kinds = PALLAS_DEVICE_KINDS
    if (lowering is not None
            and resolve_lowering(lowering, device_kind=kind) == "mosaic"):
        kinds = MOSAIC_DEVICE_KINDS
    return kind not in kinds


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered implementation of the rank-k modification protocol."""

    name: str
    fn: Callable
    kind: str  # 'serial' | 'blocked' | 'pallas' | 'collective'
    description: str
    # Factor storage structures this backend can modify (DESIGN.md §12).
    # Dense backends index into (n, n) rows/panels — handing them a
    # BlockTriDiagStorage cannot work even by accident, so the funnel
    # rejects the pairing up front instead of letting shape errors escape
    # from deep inside a kernel trace.
    structures: Tuple[str, ...] = ("dense",)

    def __call__(self, L, V, *, sigma, panel, interpret, precision=None,
                 **opts):
        precision = Precision.parse(precision)
        if precision is not None:
            # Storage casts happen at the funnel: every backend sees inputs
            # already in the policy's storage dtype, and returns it.
            L = precision.cast_storage(L)
            V = precision.cast_storage(V)
        return self.fn(L, V, sigma=sigma, panel=panel, interpret=interpret,
                       precision=precision, **opts)


_REGISTRY: Dict[str, Backend] = {}


def register(name: str, *, kind: str, description: str,
             structures: Tuple[str, ...] = ("dense",)):
    """Decorator registering ``fn`` as backend ``name``."""

    def deco(fn: Callable) -> Callable:
        if name in _REGISTRY:
            raise ValueError(f"backend {name!r} already registered")
        _REGISTRY[name] = Backend(name, fn, kind, description, structures)
        return fn

    return deco


def get(name: str) -> Backend:
    """Look up a backend; raises ValueError naming the valid set."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"method must be one of {methods()}, got {name!r}"
        ) from None


def names(structure: Optional[str] = None) -> Tuple[str, ...]:
    """Registered backend names, registration order.

    With ``structure=`` given, only the backends valid for that factor
    storage structure ('dense', 'blocktridiag', ...). No argument keeps the
    historical meaning: every registered backend.
    """
    if structure is None:
        return tuple(_REGISTRY)
    return tuple(n for n, b in _REGISTRY.items() if structure in b.structures)


def methods(structure: Optional[str] = None) -> Tuple[str, ...]:
    """Valid ``method=`` strings: every backend plus the 'auto' heuristic.

    ``structure=`` narrows to the methods valid for one storage structure —
    'auto' is always valid (it resolves per structure).
    """
    return names(structure) + ("auto",)


def resolve(
    method: str,
    *,
    n: int,
    panel: int = 256,
    interpret: Optional[bool] = None,
    device_kind: Optional[str] = None,
    structure: str = "dense",
) -> str:
    """Map ``method`` (possibly 'auto') to a concrete backend name.

    An explicit ``method`` must support ``structure`` — a dense-only
    backend asked to modify structured storage raises immediately with the
    valid set for that structure (the error a user can act on, instead of a
    shape mismatch from inside a kernel trace).

    The dense 'auto' heuristic prefers the single-launch fused kernel on
    EVERY Pallas-capable device (or under explicitly requested interpret
    mode): the Mosaic lowering on TPU, the portable lowering on
    gpu/cuda/rocm — the paper's actual target hardware (see
    ``resolve_lowering``). Otherwise the pure-JAX paths: the serial oracle
    for problems under two panels (where panelling buys nothing) and the
    transform-GEMM driver beyond.

    The 'blocktridiag' structure has one kernel and one pure-jnp twin: the
    block-chain Pallas kernel wherever Pallas can lower it (or under
    interpret mode), the lax.scan reference elsewhere.
    """
    if method != "auto":
        backend = get(method)  # validate the name first
        if structure not in backend.structures:
            raise ValueError(
                f"method {method!r} supports structures "
                f"{backend.structures}, not {structure!r}; valid methods "
                f"for {structure!r}: {methods(structure)}")
        return method
    if device_kind is None:
        device_kind = _current_device_kind()
    device_kind = device_kind.lower()
    if structure == "blocktridiag":
        if device_kind in PALLAS_DEVICE_KINDS or interpret:
            return "blocktridiag"
        return "blocktridiag_ref"
    if device_kind in PALLAS_DEVICE_KINDS or interpret:
        return "fused"
    if n < 2 * panel:
        return "reference"
    return "gemm"


def dispatch(L, V, *, sigma, method, panel, interpret, precision=None,
             **opts):
    """Resolve + run: the single funnel every consumer's update flows through.

    ``L`` is either a dense (n, n) / (B, n, n) array or a ``FactorStorage``
    (anything carrying a ``structure`` attribute). The heuristic's n is the
    factor ORDER — ``L.shape[-1]`` for dense (``shape[0]`` would read the
    batch count off a (B, n, n) leaf reaching the funnel directly), the
    storage's own ``n`` otherwise.

    Observability (DESIGN.md §13): every dispatch records its resolve
    decision and sign into ``repro.obs`` — labeled by
    backend/lowering/structure/dtype/sign, the axes the conformance tables
    slice by. Dispatch runs at TRACE time (the funnel sits inside the
    consumers' jits), so like the kernel launch counters this is a
    trace-time count: one per traced modification, not per cached
    re-execution.
    """
    structure = getattr(L, "structure", "dense")
    n = L.shape[-1] if structure == "dense" else L.n
    name = resolve(method, n=n, panel=panel, interpret=interpret,
                   structure=structure)

    policy = Precision.parse(precision)
    storage_dt = L.dtype if policy is None else policy.storage_for(L.dtype)
    lowering = (resolve_lowering(opts.get("lowering"))
                if name in ("fused", "sharded") else "none")
    try:  # sigma may be a tracer when a consumer jits over it
        sign = "up" if float(sigma) > 0 else "down"
    except Exception:
        sign = "traced"
    labels = dict(backend=name, structure=structure, lowering=lowering,
                  dtype=str(jax.numpy.dtype(storage_dt)), sign=sign)
    obs_metrics.counter("repro.backends.resolve", method=method,
                        **labels).inc()
    return get(name)(L, V, sigma=sigma, panel=panel, interpret=interpret,
                     precision=precision, **opts)


# ---------------------------------------------------------------------------
# Registered implementations. Lazy imports keep the pure-JAX core free of
# kernel/distributed dependencies until those paths actually run.
# ---------------------------------------------------------------------------


@register("reference", kind="serial",
          description="serial hyperbolic sweeps, O(k n^2) (paper Alg. 1)")
def _reference(L, V, *, sigma, panel, interpret, precision=None, **opts):
    del panel, interpret, opts
    from repro.core import ref

    if precision is None:
        return ref.chol_update_ref(L, V, sigma=sigma)
    # The serial oracle has no tile structure: the whole sweep runs in the
    # accumulation dtype, and only the returned factor is storage-typed.
    out = ref.chol_update_ref(precision.up(L), precision.up(V), sigma=sigma)
    return precision.down(out, like=L)


@register("gemm", kind="blocked",
          description="panelled, transform-GEMM panel apply (TPU-native)")
def _gemm(L, V, *, sigma, panel, interpret, precision=None, **opts):
    del interpret, opts
    from repro.core import blocked

    return blocked.chol_update_blocked(L, V, sigma=sigma, panel=panel,
                                       precision=precision)


@register("fused", kind="pallas",
          description="single-launch pipelined Pallas kernel, one body with "
                      "two lowerings: lowering='auto'|'mosaic'|'portable' "
                      "(DESIGN.md §5)")
def _fused(L, V, *, sigma, panel, interpret, precision=None, **opts):
    if L.ndim == 3:
        # A stacked fleet of single-tile members, which
        # ``api.chol_update_batched`` sends whole only under the Mosaic
        # lowering: the fleet kernel has nothing else to take from ``opts``.
        from repro.kernels import fleet as kernel_fleet

        return kernel_fleet.chol_update_fleet(L, V, sigma=sigma,
                                              interpret=interpret,
                                              precision=precision)
    from repro.kernels import fused as kernel_fused

    return kernel_fused.chol_update_fused(L, V, sigma=sigma, panel=panel,
                                          interpret=interpret,
                                          precision=precision, **opts)


@register("blocktridiag", kind="pallas", structures=("blocktridiag",),
          description="block-chain Pallas kernel for block-bidiagonal "
                      "factors: ONE launch per sign block, O(n*b) bytes "
                      "(DESIGN.md §12)")
def _blocktridiag(L, V, *, sigma, panel, interpret, precision=None, **opts):
    del panel  # the chain's tile size is the storage's block size
    opts.pop("lowering", None)  # single portable lowering; accepted + ignored
    from repro.kernels import blocktridiag as kernel_btd

    return kernel_btd.chol_update_blocktridiag(L, V, sigma=sigma,
                                               interpret=interpret,
                                               precision=precision, **opts)


@register("blocktridiag_ref", kind="blocked", structures=("blocktridiag",),
          description="pure-jnp lax.scan twin of the block-chain kernel "
                      "(panel_diag + transform-GEMM apply per block)")
def _blocktridiag_ref(L, V, *, sigma, panel, interpret, precision=None,
                      **opts):
    del panel, interpret
    opts.pop("lowering", None)
    from repro.core import structure

    return structure.chol_update_blocktridiag_ref(L, V, sigma=sigma,
                                                  precision=precision, **opts)


@register("sharded", kind="collective",
          description="column-sharded multi-device driver composing the "
                      "fused kernel (DESIGN.md §4+§7); requires mesh=")
def _sharded(L, V, *, sigma, panel, interpret, precision=None, mesh=None,
             axis="model", **opts):
    if mesh is None:
        raise ValueError("method='sharded' requires a mesh= argument")
    from repro.core import distributed

    return distributed.chol_update_sharded(L, V, sigma=sigma, mesh=mesh,
                                           axis=axis, panel=panel,
                                           interpret=interpret,
                                           precision=precision, **opts)
