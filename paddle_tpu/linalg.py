"""Linear-algebra ops (ref: ``python/paddle/tensor/linalg.py``,
``paddle.linalg`` namespace).

Decompositions lower to XLA's native TPU implementations (QR/SVD/eigh run
on-chip; nonsymmetric ``eig`` has no TPU lowering anywhere, so it round-trips
through the host LAPACK — same behaviour the reference gets by running eig on
CPU). All functions are jit-safe except where noted.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "cholesky", "cholesky_solve", "cond", "corrcoef", "cov", "det", "slogdet",
    "eig", "eigh", "eigvals", "eigvalsh", "householder_product", "inv",
    "lstsq", "lu", "lu_unpack", "matrix_exp", "matrix_power", "matrix_rank",
    "multi_dot", "norm", "pinv", "qr", "solve", "svd", "svdvals",
    "triangular_solve", "vector_norm", "matrix_norm", "dist",
]


def cholesky(x, upper=False):
    L = jnp.linalg.cholesky(x)
    return jnp.swapaxes(L, -1, -2).conj() if upper else L


def cholesky_solve(x, y, upper=False):
    """Solve A @ out = x given the Cholesky factor y of A."""
    if upper:
        y = jnp.swapaxes(y, -1, -2).conj()
    z = jax.scipy.linalg.solve_triangular(y, x, lower=True)
    return jax.scipy.linalg.solve_triangular(
        jnp.swapaxes(y, -1, -2).conj(), z, lower=False)


def det(x):
    return jnp.linalg.det(x)


def slogdet(x):
    sign, logabs = jnp.linalg.slogdet(x)
    return sign, logabs


def inv(x):
    return jnp.linalg.inv(x)


def pinv(x, rcond=1e-15, hermitian=False):
    return jnp.linalg.pinv(x, rtol=rcond, hermitian=hermitian)


def solve(x, y):
    return jnp.linalg.solve(x, y)


def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False):
    """Ref signature: solves x @ out = y with x triangular."""
    a = jnp.swapaxes(x, -1, -2) if transpose else x
    return jax.scipy.linalg.solve_triangular(
        a, y, lower=not upper if not transpose else upper,
        unit_diagonal=unitriangular)


def lstsq(x, y, rcond=None, driver=None):
    sol, res, rank, sv = jnp.linalg.lstsq(x, y, rcond=rcond)
    return sol, res, rank, sv


def qr(x, mode="reduced"):
    return jnp.linalg.qr(x, mode=mode)


def svd(x, full_matrices=False):
    return jnp.linalg.svd(x, full_matrices=full_matrices)


def svdvals(x):
    return jnp.linalg.svd(x, compute_uv=False)


def eigh(x, UPLO="L"):
    return jnp.linalg.eigh(x, UPLO=UPLO)


def eigvalsh(x, UPLO="L"):
    return jnp.linalg.eigvalsh(x, UPLO=UPLO)


def _host_eig(x, compute_vectors):
    """Nonsymmetric eig has no TPU/XLA lowering — evaluate on the host.

    Eager calls go straight through numpy (works on every backend, with or
    without host-callback support); traced calls use
    pure_callback, which requires a backend with host send/recv.
    """
    cdtype = jnp.complex64 if x.dtype in (jnp.float32, jnp.complex64) else jnp.complex128
    if not isinstance(x, jax.core.Tracer):
        a = np.asarray(jax.device_get(x))
        # keep results on the host CPU device: some TPU transports cannot
        # round-trip complex arrays, and downstream eig consumers are
        # host-side anyway
        cpu = jax.devices("cpu")[0]
        if compute_vectors:
            w, v = np.linalg.eig(a)
            return (jax.device_put(w.astype(cdtype), cpu),
                    jax.device_put(v.astype(cdtype), cpu))
        return jax.device_put(np.linalg.eigvals(a).astype(cdtype), cpu)
    if compute_vectors:
        def cb(a):
            w, v = np.linalg.eig(np.asarray(a))
            return w.astype(cdtype), v.astype(cdtype)

        shape = (jax.ShapeDtypeStruct(x.shape[:-1], cdtype),
                 jax.ShapeDtypeStruct(x.shape, cdtype))
        return jax.pure_callback(cb, shape, x, vmap_method="sequential")

    def cb(a):
        return np.linalg.eigvals(np.asarray(a)).astype(cdtype)

    return jax.pure_callback(
        cb, jax.ShapeDtypeStruct(x.shape[:-1], cdtype), x,
        vmap_method="sequential")


def eig(x):
    return _host_eig(x, compute_vectors=True)


def eigvals(x):
    return _host_eig(x, compute_vectors=False)


def lu(x, pivot=True):
    """Returns (LU, pivots) packed like the reference (1-based pivots)."""
    lu_, piv = jax.scipy.linalg.lu_factor(x)
    return lu_, piv + 1


def lu_unpack(lu_data, lu_pivots, unpack_ludata=True, unpack_pivots=True):
    """2-D unpack of ``lu`` output into (P, L, U); batch via jax.vmap."""
    m, n = lu_data.shape[-2:]
    k = min(m, n)
    L = jnp.tril(lu_data, -1)[..., :, :k] + jnp.eye(m, k, dtype=lu_data.dtype)
    U = jnp.triu(lu_data)[..., :k, :]
    piv = lu_pivots - 1  # back to 0-based swap sequence

    def body(i, perm):
        j = piv[i]
        pi, pj = perm[i], perm[j]
        return perm.at[i].set(pj).at[j].set(pi)

    perm = lax.fori_loop(0, piv.shape[0], body, jnp.arange(m))
    # rows of A permuted by perm: A = P @ L @ U with P[perm[i], i] = 1
    P = jax.nn.one_hot(perm, m, dtype=lu_data.dtype).T
    return P, L, U


def matrix_exp(x):
    return jax.scipy.linalg.expm(x)


def matrix_power(x, n):
    return jnp.linalg.matrix_power(x, n)


def matrix_rank(x, tol=None, hermitian=False):
    """Count of singular values above ``tol`` — ``tol`` is ABSOLUTE
    (reference semantics), default eps-scaled like numpy."""
    if hermitian:
        s = jnp.abs(jnp.linalg.eigvalsh(x))
    else:
        s = jnp.linalg.svd(x, compute_uv=False)
    if tol is None:
        eps = jnp.finfo(x.dtype).eps
        tol = s.max(axis=-1, keepdims=True) * max(x.shape[-2:]) * eps
    return jnp.sum(s > tol, axis=-1)


def householder_product(x, tau):
    """Q from the compact Householder form, 2-D (ref:
    paddle.linalg.householder_product); batch via jax.vmap."""
    m, n = x.shape
    Q = jnp.eye(m, dtype=x.dtype)
    for i in range(n):
        # rank-1 update Q @ (I - tau v v*) = Q - tau (Q v) v*
        v = jnp.where(jnp.arange(m) > i, x[:, i], 0.0).at[i].set(1.0)
        Q = Q - tau[i] * jnp.outer(Q @ v, v.conj())
    return Q[:, :n]


def multi_dot(xs):
    out = xs[0]
    for x in xs[1:]:
        out = out @ x
    return out


def cond(x, p=None):
    if p is None or p == 2:
        s = svdvals(x)
        return s[..., 0] / s[..., -1]
    return norm(x, p=p, axis=(-2, -1)) * norm(inv(x), p=p, axis=(-2, -1))


def _keep_all_dims(val, ndim):
    return val.reshape((1,) * ndim)


def norm(x, p=None, axis=None, keepdim=False):
    """Unified vector/matrix norm (ref: paddle.linalg.norm)."""
    if p == "fro":
        ax = tuple(axis) if isinstance(axis, (tuple, list)) else \
            (axis,) if axis is not None else None
        return jnp.sqrt(jnp.sum(jnp.square(jnp.abs(x)), axis=ax,
                                keepdims=keepdim))
    if p == "nuc":
        if axis is not None and not isinstance(axis, (tuple, list)):
            raise ValueError("nuclear norm needs a 2-axis tuple, got "
                             f"axis={axis!r}")
        ax = tuple(a % x.ndim for a in axis) if axis is not None \
            else (x.ndim - 2, x.ndim - 1)
        xm = jnp.moveaxis(x, ax, (-2, -1))
        out = jnp.sum(jnp.linalg.svd(xm, compute_uv=False), axis=-1)
        if keepdim:
            out = jnp.expand_dims(jnp.expand_dims(out, -1), -1)
            out = jnp.moveaxis(out, (-2, -1), ax)
        return out
    if isinstance(axis, (tuple, list)) and len(axis) == 2:
        return jnp.linalg.norm(x, ord=p, axis=tuple(axis), keepdims=keepdim)
    if p is None:
        p = 2
    if axis is None:
        out = jnp.linalg.norm(x.reshape(-1), ord=p)
        return _keep_all_dims(out, x.ndim) if keepdim else out
    return jnp.linalg.norm(x, ord=p, axis=axis, keepdims=keepdim)


def vector_norm(x, p=2, axis=None, keepdim=False):
    if axis is None:
        out = jnp.linalg.norm(x.reshape(-1), ord=p)
        return _keep_all_dims(out, x.ndim) if keepdim else out
    return jnp.linalg.norm(x, ord=p, axis=axis, keepdims=keepdim)


def matrix_norm(x, p="fro", axis=(-2, -1), keepdim=False):
    return norm(x, p=p, axis=axis, keepdim=keepdim)


def dist(x, y, p=2):
    return vector_norm(x - y, p=p)


def cov(x, rowvar=True, ddof=True, fweights=None, aweights=None):
    return jnp.cov(x, rowvar=rowvar, ddof=1 if ddof else 0,
                   fweights=fweights, aweights=aweights)


def corrcoef(x, rowvar=True):
    return jnp.corrcoef(x, rowvar=rowvar)


def ormqr(x, tau, y, left=True, transpose=False):
    """Ref linalg.ormqr: multiply ``y`` by the implicit Q of the
    householder factors ``(x, tau)`` (geqrf layout). Reflectors are applied
    directly — k rank-1 updates, no m x m Q materialisation."""
    from jax import lax as _lax

    m, k = x.shape[-2], x.shape[-1]
    rows = jnp.arange(m)
    forward = (left and transpose) or (not left and not transpose)

    def body(step, out):
        # Q = H_0 H_1 ... H_{k-1}; iterate in the order Q (or Q^T) applies
        i = step if forward else k - 1 - step
        col = _lax.dynamic_index_in_dim(x, i, axis=-1, keepdims=False)
        v = jnp.where(rows < i, 0.0, jnp.where(rows == i, 1.0, col))
        t = _lax.dynamic_index_in_dim(tau, i, axis=-1,
                                      keepdims=False)[..., None, None]
        if left:
            proj = jnp.einsum("...m,...mn->...n", v, out)
            return out - t * v[..., :, None] * proj[..., None, :]
        proj = jnp.einsum("...nm,...m->...n", out, v)
        return out - t * proj[..., :, None] * v[..., None, :]

    # one traced body, k sequential steps — trace size O(1) in k
    return _lax.fori_loop(0, k, body, y)


def svd_lowrank(x, q=6, niter=2, M=None):
    """Ref linalg.svd_lowrank — randomized low-rank SVD (Halko et al.):
    subspace iteration with QR re-orthonormalisation; all matmul/QR, so it
    maps straight onto the MXU. Deterministic under the global seed."""
    from paddle_tpu.core.random import next_key
    if M is not None:
        x = x - M
    m, n = x.shape[-2], x.shape[-1]
    k = min(q, m, n)
    g = jax.random.normal(next_key(), x.shape[:-2] + (n, k), jnp.float32)
    y = x @ g
    qmat, _ = jnp.linalg.qr(y)
    for _ in range(niter):
        z = jnp.swapaxes(x, -1, -2) @ qmat
        z, _ = jnp.linalg.qr(z)
        y = x @ z
        qmat, _ = jnp.linalg.qr(y)
    b = jnp.swapaxes(qmat, -1, -2) @ x
    u_b, s, vh = jnp.linalg.svd(b, full_matrices=False)
    return qmat @ u_b, s, jnp.swapaxes(vh, -1, -2)
