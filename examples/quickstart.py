"""Quickstart: the rank-k Cholesky up/down-date public API.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import (
    CholFactor,
    backends,
    chol_downdate,
    chol_factor,
    chol_solve,
    chol_update,
    modify_error,
    resolve_backend_for,
)
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache()

# --- Build an SPD matrix and its upper Cholesky factor (A = L^T L). -------
rng = np.random.default_rng(0)
n, k = 512, 16
B = rng.uniform(size=(n, n)).astype(np.float32)
A = jnp.asarray(B.T @ B + np.eye(n, dtype=np.float32))
L = chol_factor(A)
V = jnp.asarray(rng.uniform(size=(n, k)).astype(np.float32))

# --- Rank-16 update: O(k n^2) instead of refactorizing in O(n^3). ---------
L_up = chol_update(L, V, method="gemm")           # TPU-native panel GEMM
err = modify_error(L_up, L, V, sigma=1)           # paper's error metric
print(f"update:   max|A~ - L~^T L~| = {float(err):.3e}")

# The same result via the paper-faithful element-wise panel path:
L_up2 = chol_update(L, V, method="paper")
print(f"paths agree to {float(jnp.max(jnp.abs(L_up - L_up2))):.3e}")

# --- Downdate: remove V V^T again and recover the original factor. --------
L_back = chol_downdate(L_up, V, method="gemm")
print(f"roundtrip: max|L - L_back| = {float(jnp.max(jnp.abs(L - L_back))):.3e}")

# --- Use the maintained factor: solve A~ x = b without refactorizing. -----
b = jnp.asarray(rng.uniform(size=(n,)).astype(np.float32))
x = chol_solve(L_up, b)
resid = jnp.max(jnp.abs((A + V @ V.T) @ x - b))
print(f"solve:    max residual = {float(resid):.3e}")

# --- Pallas kernel path (interpret mode on CPU, Mosaic on TPU). -----------
L_pal = chol_update(L, V, method="pallas_gemm", panel=128)
print(f"pallas:   max|gemm - pallas| = {float(jnp.max(jnp.abs(L_up - L_pal))):.3e}")

# --- The stateful engine: one CholFactor, every op on the same object. -----
# Backends are a registry ('auto' resolves by device/size heuristics); the
# factor is a pytree, so it jits, vmaps, and lives in optimizer state.
print(f"registered backends: {backends.names()}")
f = CholFactor.from_matrix(A, panel=128)   # backend='auto'
print(f"{f!r} -> auto resolves to {resolve_backend_for(f)!r}")
f = f.update(V)                            # A + V V^T, no refactorization
x2 = f.solve(b)                            # same two triangular solves
print(f"factor:   max|x - x_factor| = {float(jnp.max(jnp.abs(x - x2))):.3e}")
print(f"logdet:   {float(f.logdet()):.2f}")
guarded, ok = f.downdate_guarded(100.0 * V)  # PD guard refuses bad downdates
print(f"guarded downdate of an infeasible V: ok={bool(ok)} (factor unchanged)")
f = f.downdate(V)                          # back to the original statistics
print(f"object roundtrip: max|L - f.data| = "
      f"{float(jnp.max(jnp.abs(L - f.data))):.3e}")
