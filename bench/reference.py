"""The plain reference: preconditioned CG and the true residual.

Straight numpy/scipy on the host, in float64, written from the textbook
algorithm (CG from x0 = 0, M = diag(A) or the identity, stopping when the
recursive residual satisfies ||r|| <= tol ||b|| or after ``maxiter``
iterations).  It imports nothing of the program under test.

The control of ``bench/control.py`` computes the same algorithm one
precision lower: in float32 arithmetic (the operator given as float32), or
with every stored array rounded by ``store`` (operator values, M^-1, the
vectors after each update) to bfloat16 (:func:`bf16_store`).
"""
from __future__ import annotations

from typing import Callable

import numpy as np


def operator(indptr, indices, data, dtype=np.float64):
    """The CSR operator as a scipy matrix of ``dtype``."""
    from scipy.sparse import csr_matrix
    n = len(indptr) - 1
    return csr_matrix((np.asarray(data, dtype), indices, indptr), shape=(n, n))


def bf16_store(v: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and hold the result as float32."""
    import ml_dtypes
    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def cg(A, b: np.ndarray, tol: float, maxiter: int, precond: str = "jacobi",
       store: Callable[[np.ndarray], np.ndarray] | None = None
       ) -> tuple[np.ndarray, int, float]:
    """Solve ``A x = b`` by CG preconditioned by ``precond`` (``"jacobi"``
    or ``"none"``); return ``(x, iterations, rel)`` with ``rel`` the final
    recursive residual over ``||b||``.

    ``A`` is a scipy CSR matrix whose dtype sets the arithmetic; ``store``
    (None: keep every value at full precision) rounds what is stored."""
    if precond not in ("jacobi", "none"):
        raise ValueError(f"no reference for preconditioner {precond!r}")
    keep = store if store is not None else (lambda v: v)
    dt = A.dtype
    if store is not None:
        A = A.copy()
        A.data = keep(A.data).astype(dt)
    dinv = keep((1.0 / A.diagonal()).astype(dt) if precond == "jacobi"
                else np.ones(A.shape[0], dt))
    b = keep(np.asarray(b, dt))
    x = np.zeros_like(b)
    r = b.copy()
    z = keep(dinv * r)
    p = z.copy()
    rz, rr = r @ z, r @ r
    bnorm = max(float(np.sqrt(rr)), 1e-30)
    tol2 = (tol * bnorm) ** 2
    k = 0
    while k < maxiter and rr > tol2:
        ap = keep(A @ p)
        alpha = rz / (p @ ap)
        x = keep(x + alpha * p)
        r = keep(r - alpha * ap)
        z = keep(dinv * r)
        rz_new, rr = r @ z, r @ r
        p = keep(z + (rz_new / rz) * p)
        rz = rz_new
        k += 1
    return np.asarray(x, np.float64), k, float(np.sqrt(rr)) / bnorm


def true_rel(A64, x: np.ndarray, b: np.ndarray) -> float:
    """Host f64 true relative residual ``||b - A x|| / ||b||``."""
    b = np.asarray(b, np.float64)
    r = b - A64 @ np.asarray(x, np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def iterate_err(x: np.ndarray, x_ref: np.ndarray) -> float:
    """Widest entrywise gap to the reference iterate, over its largest
    entry: ``max |x - x_ref| / max |x_ref|``."""
    x_ref = np.asarray(x_ref, np.float64)
    gap = np.abs(np.asarray(x, np.float64) - x_ref).max()
    return float(gap / max(np.abs(x_ref).max(), 1e-300))
