"""The phase names of a dense modification (``repro.obs.phases``) and the
spans of ``repro.obs`` on the profiler's clock.

A phase scope is metadata only: the compiled program is the same with and
without it once every ``metadata={...}`` field is stripped. The kernels
carry stable names, one per sign. A ``repro.obs.span`` shows in a
``jax.profiler.trace`` as a host event, and ``repro.obs`` imports without
jax.
"""
import contextlib
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs
from repro.core import CholFactor
from repro.kernels import fused as F
from repro.obs import phases

N, K, PANEL = 80, 2, 32   # pads to 96: every phase has work
_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")


def strip_metadata(hlo_text: str) -> str:
    return _METADATA.sub("", hlo_text)


def _args():
    rng = np.random.default_rng(0)
    A = rng.standard_normal((N, N)).astype(np.float32)
    L = np.linalg.cholesky(A @ A.T + N * np.eye(N, dtype=np.float32)).T
    V = 0.1 * rng.standard_normal((N, K)).astype(np.float32)
    return jnp.asarray(L), jnp.asarray(V)


PROGRAMS = {
    "update": lambda L, V: F.chol_update_fused(
        L, V, sigma=1, panel=PANEL, interpret=True),
    "downdate": lambda L, V: F.chol_update_fused(
        L, V, sigma=-1, panel=PANEL, interpret=True),
    "downdate_guarded": lambda L, V: CholFactor(
        L, panel=PANEL, interpret=True, backend="fused").downdate_guarded(V),
}


def compiled_text(program, *args) -> str:
    jax.clear_caches()
    return jax.jit(PROGRAMS[program]).lower(*args).compile().as_text()


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_phase_scopes_leave_the_compiled_program_unchanged(program,
                                                           monkeypatch):
    L, V = _args()
    texts = []
    for with_scopes in (True, False):
        # One call site for both: the text's stack-frame table holds it.
        with monkeypatch.context() as m:
            if not with_scopes:
                m.setattr(phases, "scope",
                          lambda name: contextlib.nullcontext())
            texts.append(compiled_text(program, L, V))
    scoped, bare = texts
    want = {phases.PAD, phases.KERNEL, phases.UNPAD}
    if program == "downdate_guarded":
        want.add(phases.GUARD)
    assert all(p in scoped for p in want)
    assert not any(p in bare for p in phases.PHASES)
    assert strip_metadata(scoped) == strip_metadata(bare)


@pytest.mark.parametrize("sigma,name", [(1, "chol_fused_update"),
                                        (-1, "chol_fused_downdate")])
def test_fused_kernel_has_a_stable_name_per_sign(sigma, name):
    assert F.kernel_name(sigma) == name
    L, V = _args()
    text = compiled_text("update" if sigma > 0 else "downdate", L, V)
    assert f"/{phases.KERNEL}/{name}/" in text


def test_phases_are_defined_once():
    assert repro.obs.PHASES == phases.PHASES == (
        phases.PAD, phases.KERNEL, phases.UNPAD, phases.GUARD)
    assert all(p.startswith("repro.") for p in phases.PHASES)


def test_span_is_a_host_event_of_a_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with repro.obs.span("repro.test_span", layer="obs"):
            jnp.ones(4).block_until_ready()
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_serialized_xspace(path.read_bytes())
    names = [e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("repro.test_span") == 1
    # The ring records it as before.
    assert repro.obs.RECORDER.events()[-1].name == "repro.test_span"


def test_repro_obs_imports_without_jax():
    code = (
        "import sys\n"
        "class Block:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'jax' or name.startswith('jax.'):\n"
        "            raise ImportError('jax is blocked')\n"
        "sys.meta_path.insert(0, Block())\n"
        "import repro.obs\n"
        "with repro.obs.span('x'):\n"
        "    pass\n"
        "assert len(repro.obs.RECORDER) == 1\n"
        "assert 'jax' not in sys.modules\n"
        "print(repro.obs.PHASES)\n")
    src = str(Path(repro.obs.__file__).resolve().parents[2])
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={"PYTHONPATH": src}, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "repro.guard" in out.stdout
