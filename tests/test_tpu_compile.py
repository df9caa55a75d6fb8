"""Compile the main-path kernels for a described TPU v5e (no chip needed).

Interpret mode cannot see what only the TPU compiler refuses: primitives
Mosaic has no lowering for, blocks that do not tile, VMEM overruns. Each
test here lowers one kernel at the sizes ``chip_smoke.py`` runs and
compiles it for one chip of a described ``v5e:2x2`` topology, then checks
that the compiled program holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CholFactor
from repro.kernels import blocktridiag as btd_k
from repro.kernels import fused as F
from repro.kernels import sharded as sharded_k
from repro.obs import phases

K_RANK = 16
PANEL = 256
N_DENSE = 5120          # the paper's n = 5000, padded to whole panels
N_LARGE = 20224         # n = 20000, padded: the V^T scratch at scale
FLEET, N_FLEET, FLEET_PANEL = 2048, 256, 128   # members of two panels
KALMAN_T, KALMAN_D = 4096, 4                    # horizon x state dim
N_SHARDED, SHARDS = 32768, 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n", [N_DENSE, N_LARGE, N_SHARDED])
@pytest.mark.parametrize("precision", [None, "bf16"])
def test_fused_mosaic_compiles(one_chip, n, precision):
    """The dense kernel at the smoke's sizes; N_SHARDED is the one-chip
    reference the four-chip phase compares against."""
    dt = jnp.bfloat16 if precision else jnp.float32
    acc = jnp.float32 if precision else None
    _compile(lambda L, vt: F._fused_call(
        L, vt, sigma=1, panel=PANEL, interpret=False, accum_dtype=acc,
        lowering="mosaic"),
        one_chip, ((n, n), dt), ((K_RANK, n), dt))


def test_fused_mosaic_fleet_vmap_compiles(one_chip):
    """A fleet whose members span more than one panel keeps the fused
    kernel vmapped over its members (the PrefetchScalarGridSpec call
    batched); single-tile members take the fleet kernel instead."""
    _compile(jax.vmap(lambda L, vt: F._fused_call(
        L, vt, sigma=1, panel=FLEET_PANEL, interpret=False,
        lowering="mosaic")),
        one_chip, ((FLEET, N_FLEET, N_FLEET), jnp.float32),
        ((FLEET, K_RANK, N_FLEET), jnp.float32))


def test_fused_mosaic_refuses_untileable_panel():
    """A compiled Mosaic call whose panel cannot tile raises at trace time,
    naming the constraint (no chip or topology needed to see it)."""
    L = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    vt = jax.ShapeDtypeStruct((K_RANK, 256), jnp.float32)
    with pytest.raises(ValueError, match="panel % 128"):
        jax.eval_shape(lambda L, vt: F._fused_call(
            L, vt, sigma=1, panel=64, interpret=False, lowering="mosaic"),
            L, vt)


@pytest.mark.parametrize("batch", [0, 8])
@pytest.mark.parametrize("precision", [None, "bf16"])
def test_blocktridiag_compiles(one_chip, batch, precision):
    dt = jnp.bfloat16 if precision else jnp.float32
    acc = jnp.float32 if precision else None
    nb, b = KALMAN_T, KALMAN_D
    lead = (batch,) if batch else ()
    fn = lambda d, o, v: btd_k._btd_call(d, o, v, sigma=1, interpret=False,
                                         accum_dtype=acc)
    _compile(jax.vmap(fn) if batch else fn, one_chip,
             (lead + (nb, b, b), dt), (lead + (nb, b, b), dt),
             (lead + (nb + 1, K_RANK, b), dt))


@pytest.mark.parametrize("k", [K_RANK, 1])
def test_sharded_panel_kernel_compiles(one_chip, k):
    """One shard's panel phase of the column-sharded factor."""
    n, w_loc = N_SHARDED, N_SHARDED // SHARDS
    n_panels, pk = n // PANEL, PANEL + k
    _compile(lambda L, T, D, vt: sharded_k.panel_apply_sharded(
        L, T, D, vt, tile_off=jnp.int32(1), panel=PANEL, interpret=False,
        lowering="mosaic"), one_chip,
        ((n, w_loc), jnp.float32), ((n_panels, pk, pk), jnp.float32),
        ((n_panels, PANEL, PANEL), jnp.float32),
        ((n_panels, k, w_loc), jnp.float32))


@pytest.mark.parametrize("guarded", [False, True])
def test_phase_scopes_leave_the_tpu_program_unchanged(one_chip, guarded,
                                                      monkeypatch):
    """The benchmark's two programs at a padded order, compiled for the
    chip with and without the phase scopes (``repro.obs.phases``): the
    same program once ``metadata`` is stripped, the kernel named by sign."""

    def step(L, V):
        f = CholFactor(L, panel=PANEL, interpret=False, backend="fused",
                       lowering="mosaic")
        return f.downdate_guarded(V) if guarded else f.update(V)

    texts = []
    for with_scopes in (True, False):
        with monkeypatch.context() as m:
            if not with_scopes:
                m.setattr(phases, "scope",
                          lambda name: contextlib.nullcontext())
            jax.clear_caches()
            texts.append(_compile(step, one_chip, ((1000, 1000), jnp.float32),
                                  ((1000, K_RANK), jnp.float32)).as_text())
    scoped, bare = (re.sub(r",? ?metadata=\{[^}]*\}", "", t) for t in texts)
    assert scoped == bare
    assert texts[0] != texts[1] and phases.KERNEL in texts[0]
    name = F.kernel_name(-1 if guarded else 1)
    assert f"%{name}" in scoped


_BODY = re.compile(r'"body":"([^"]*)"')
_LOCATION_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames")


def _kernel_free_text(text: str) -> str:
    """Compiled HLO text without ``metadata`` and the source-location
    tables it points into (``FileNames`` to ``StackFrames``), with each
    Mosaic kernel's instruction named ``%kernel`` and its serialized body
    replaced by the body's MLIR printed without locations, the module
    named ``@kernel``."""
    import base64

    from jax._src.lib.mlir import ir

    text = "\n\n".join(b for b in text.split("\n\n")
                       if b.split("\n")[0] not in _LOCATION_TABLES)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    names = re.findall(r"^\s*(?:ROOT )?%(\S+) = [^\n]*tpu_custom_call", text,
                       flags=re.M)
    assert names
    for name in names:
        text = re.sub(rf"%{re.escape(name)}(?![\w.-])", "%kernel", text)

    def body(m):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return re.sub(r"^module @\S+", "module @kernel", asm)

    return _BODY.sub(body, text)


@pytest.mark.parametrize("program", ["update", "downdate",
                                     "downdate_guarded"])
def test_tpu_program_matches_the_unnamed_unscoped_kernel(one_chip, program,
                                                         monkeypatch):
    """With the kernel unnamed and no phase scopes the program is the one
    built before either existed; with both it is the same program but for
    ``metadata``, the kernel's instruction name and the name its Mosaic
    body carries."""

    def step(L, V):
        f = CholFactor(L, panel=PANEL, interpret=False, backend="fused",
                       lowering="mosaic")
        if program == "update":
            return f.update(V)
        return f.downdate_guarded(V) if "guarded" in program \
            else f.downdate(V)

    texts = []
    for bare in (False, True):
        with monkeypatch.context() as m:
            if bare:
                m.setattr(F, "kernel_name", lambda sigma: None)
                m.setattr(phases, "scope",
                          lambda name: contextlib.nullcontext())
            jax.clear_caches()
            texts.append(_compile(step, one_chip, ((1000, 1000), jnp.float32),
                                  ((1000, K_RANK), jnp.float32)).as_text())
    named, unnamed = texts
    name = F.kernel_name(1 if program == "update" else -1)
    assert f"%{name}" in named and name not in unnamed
    got = _kernel_free_text(named)
    assert "module @kernel" in got and "%kernel" in got
    assert got == _kernel_free_text(unnamed)


@pytest.mark.parametrize("step", ["up", "down", "read"])
def test_gathered_fleet_steps_compile_without_fleet_copies(one_chip, step):
    """The stream store's gathered flush and read steps for a fleet of
    2^18 members of order 36 at a 1024-member bucket: the update and
    downdate hold the kernel, and no step copies the fleet (an XLA
    scatter would copy it into another layout and back)."""
    from repro.stream import store as store_mod

    cap, n, m = 2**18, 36, 1024
    steps = store_mod._steps_for(FLEET_PANEL, "fused", False, None)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in
            [((cap, n, n), jnp.float32), ((m,), jnp.int32)]
            + ([((), jnp.int32), ((m, n, K_RANK), jnp.float32)]
               if step != "read" else [((m, n, 21), jnp.float32)])]
    compiled = steps.jitted[step].lower(*args).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (step != "read")
    fleet_bytes = cap * n * n * 4
    assert compiled.memory_analysis().temp_size_in_bytes < fleet_bytes / 8
    assert not re.search(rf"f32\[{cap},{n},{n}\][^\n]* copy\(", text)


@pytest.mark.parametrize("step", ["up", "down"])
@pytest.mark.parametrize("members", [16, 1024])
def test_gathered_fleet_steps_run_the_fleet_kernel(one_chip, members, step):
    """The same flush steps at both member buckets of the fleet cell run
    the fleet kernel on the gathered members in place: no member padded
    to a 128-row tile, and no copy of the gathered block into the
    kernel's layout."""
    from repro.kernels import fleet as fleet_k
    from repro.stream import store as store_mod

    cap, n = 2**18, 36
    steps = store_mod._steps_for(FLEET_PANEL, "fused", False, None)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in
            [((cap, n, n), jnp.float32), ((members,), jnp.int32),
             ((), jnp.int32), ((members, n, K_RANK), jnp.float32)]]
    text = steps.jitted[step].lower(*args).compile().as_text()
    name = fleet_k.kernel_name(1 if step == "up" else -1)
    kernel = re.search(rf"^\s*%{name}\S* = [^\n]*custom-call\((%[^,)]+)"
                       r"[^\n]*", text, flags=re.M)
    assert kernel and "tpu_custom_call" in kernel.group(0)
    assert not re.search(r"f32\[(\d+,)*128,128\]", text)
    # The gathered factors reach the kernel as a bitcast, not a copy.
    operand = re.search(rf"^\s*{re.escape(kernel.group(1))} = [^\n]*", text,
                        flags=re.M)
    assert f"f32[{n},{n},{members}]" in operand.group(0)
    assert " bitcast(" in operand.group(0)


@pytest.mark.parametrize("n,precision", [(36, None), (36, "bf16"),
                                         (128, None)])
def test_fleet_kernel_compiles(one_chip, n, precision):
    """The fleet kernel alone, 1024 members: the cell's order in both
    storage dtypes, and the largest order the dispatch admits."""
    from repro.kernels import fleet as fleet_k

    dt = jnp.bfloat16 if precision else jnp.float32
    _compile(lambda L, V: fleet_k.chol_update_fleet(
        L, V, sigma=-1, interpret=False, precision=precision), one_chip,
        ((1024, n, n), dt), ((1024, n, K_RANK), dt))
