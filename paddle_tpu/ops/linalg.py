"""Linear algebra ops (reference: operators/norm_op.cc, p_norm_op.cc,
cholesky_op.cc, svd helpers in math/, paddle.linalg namespace)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.dispatch import primitive, ensure_tensor


def norm(x, p="fro", axis=None, keepdim=False, name=None):
    x = ensure_tensor(x)
    if isinstance(axis, (list, tuple)):
        axis = tuple(axis)

    if p == "fro" or (p == 2 and axis is None):
        fn = lambda a: jnp.sqrt(jnp.sum(jnp.square(a), axis=axis,
                                        keepdims=keepdim))
    elif p == float("inf"):
        fn = lambda a: jnp.max(jnp.abs(a), axis=axis, keepdims=keepdim)
    elif p == float("-inf"):
        fn = lambda a: jnp.min(jnp.abs(a), axis=axis, keepdims=keepdim)
    elif p == 0:
        fn = lambda a: jnp.sum((a != 0).astype(a.dtype), axis=axis,
                               keepdims=keepdim)
    elif p == 1:
        fn = lambda a: jnp.sum(jnp.abs(a), axis=axis, keepdims=keepdim)
    else:
        pf = float(p)
        fn = lambda a: jnp.power(
            jnp.sum(jnp.power(jnp.abs(a), pf), axis=axis, keepdims=keepdim),
            1.0 / pf)
    return primitive(name="p_norm")(fn)(x)


def dist(x, y, p=2, name=None):
    x, y = ensure_tensor(x), ensure_tensor(y)
    diff = primitive(name="dist_sub")(jnp.subtract)(x, y)
    return norm(diff, p=p)


@primitive(name="cholesky")
def _cholesky(x, upper=False):
    L = jnp.linalg.cholesky(x)
    return jnp.swapaxes(L, -1, -2) if upper else L


def cholesky(x, upper=False, name=None):
    return _cholesky(ensure_tensor(x), upper=upper)


@primitive(name="inverse")
def _inv(x):
    return jnp.linalg.inv(x)


def inverse(x, name=None):
    return _inv(ensure_tensor(x))


inv = inverse


@primitive(name="matrix_power")
def _matrix_power(x, n=1):
    return jnp.linalg.matrix_power(x, n)


def matrix_power(x, n, name=None):
    return _matrix_power(ensure_tensor(x), n=int(n))


def det(x, name=None):
    return primitive(name="determinant")(jnp.linalg.det)(ensure_tensor(x))


def slogdet(x, name=None):
    x = ensure_tensor(x)
    sign, logabs = jnp.linalg.slogdet(x._data)
    return Tensor(jnp.stack([sign, logabs]))


def svd(x, full_matrices=False, name=None):
    """x = U @ diag(S) @ VH (paddle.linalg.svd convention: the third
    output is VH, not V)."""
    x = ensure_tensor(x)
    u, s, vh = jnp.linalg.svd(x._data, full_matrices=full_matrices)
    return Tensor(u), Tensor(s), Tensor(vh)


def qr(x, mode="reduced", name=None):
    x = ensure_tensor(x)
    q, r = jnp.linalg.qr(x._data, mode=mode)
    return Tensor(q), Tensor(r)


def eigh(x, UPLO="L", name=None):
    x = ensure_tensor(x)
    w, v = jnp.linalg.eigh(x._data, symmetrize_input=True)
    return Tensor(w), Tensor(v)


def eigvalsh(x, UPLO="L", name=None):
    x = ensure_tensor(x)
    return Tensor(jnp.linalg.eigvalsh(x._data))


@primitive(name="solve")
def _solve(a, b):
    return jnp.linalg.solve(a, b)


def solve(x, y, name=None):
    return _solve(ensure_tensor(x), ensure_tensor(y))


@primitive(name="triangular_solve")
def _triangular_solve(a, b, upper=True, transpose=False, unitriangular=False):
    import jax.scipy.linalg as jsl
    return jsl.solve_triangular(a, b, lower=not upper,
                                trans=1 if transpose else 0,
                                unit_diagonal=unitriangular)


def triangular_solve(x, y, upper=True, transpose=False, unitriangular=False,
                     name=None):
    return _triangular_solve(ensure_tensor(x), ensure_tensor(y), upper=upper,
                             transpose=transpose,
                             unitriangular=unitriangular)


def matrix_rank(x, tol=None, hermitian=False, name=None):
    x = ensure_tensor(x)
    return Tensor(jnp.linalg.matrix_rank(x._data, rtol=tol).astype("int64"))


def pinv(x, rcond=1e-15, hermitian=False, name=None):
    x = ensure_tensor(x)
    return Tensor(jnp.linalg.pinv(x._data, rtol=rcond, hermitian=hermitian))


def cond(x, p=None, name=None):
    x = ensure_tensor(x)
    return Tensor(jnp.linalg.cond(x._data, p=p))


def multi_dot(tensors, name=None):
    arrays = [ensure_tensor(t) for t in tensors]
    prim = primitive(name="multi_dot")(
        lambda *arrs: jnp.linalg.multi_dot(arrs))
    return prim(*arrays)


def cross(x, y, axis=9, name=None):
    x, y = ensure_tensor(x), ensure_tensor(y)
    if axis == 9:  # paddle default: first axis with dim 3
        axis = next(i for i, s in enumerate(x.shape) if s == 3)
    prim = primitive(name="cross")(
        lambda a, b: jnp.cross(a, b, axis=axis))
    return prim(x, y)


def histogram(x, bins=100, min=0, max=0, name=None):
    import numpy as np
    x = ensure_tensor(x)
    arr = np.asarray(x._data)
    if min == 0 and max == 0:
        min, max = float(arr.min()), float(arr.max())
    hist, _ = np.histogram(arr, bins=bins, range=(min, max))
    return Tensor(hist.astype(np.int64))


def bincount(x, weights=None, minlength=0, name=None):
    x = ensure_tensor(x)
    w = ensure_tensor(weights)._data if weights is not None else None
    return Tensor(jnp.bincount(x._data.reshape(-1), weights=w,
                               minlength=int(minlength),
                               length=None))


def lstsq(x, y, rcond=None, driver=None, name=None):
    """paddle.linalg.lstsq — least-squares solution (reference lstsq_op).

    Returns (solution, residuals, rank, singular_values) like paddle 2.x.
    Accepts batched (*, M, N) inputs via vmap over the leading dims; the
    `driver` knob is a LAPACK-backend selector with no XLA analogue and is
    ignored.
    """
    x, y = ensure_tensor(x), ensure_tensor(y)
    a, b = x._data, y._data
    solver = lambda ai, bi: jnp.linalg.lstsq(ai, bi, rcond=rcond)
    for _ in range(a.ndim - 2):
        solver = jax.vmap(solver)
    sol, res, rank, sv = solver(a, b)
    return (Tensor(sol), Tensor(res), Tensor(rank), Tensor(sv))


def lu(x, pivot=True, get_infos=False, name=None):
    """paddle.linalg.lu — LU factorization (packed LU + pivots).

    Pivots are 1-based (paddle convention: 1 <= pivots[i] <= m); infos[i]>0
    flags a zero pivot on the diagonal (singular factorization).
    """
    import jax.scipy.linalg as jsl
    x = ensure_tensor(x)
    lu_mat, piv = jsl.lu_factor(x._data)
    piv = piv + 1
    if get_infos:
        diag = jnp.diagonal(lu_mat, axis1=-2, axis2=-1)
        zero = diag == 0
        # first zero-pivot index + 1, or 0 when none (LAPACK getrf contract)
        first = jnp.argmax(zero, axis=-1) + 1
        info = jnp.where(jnp.any(zero, axis=-1), first, 0).astype(jnp.int32)
        return Tensor(lu_mat), Tensor(piv), Tensor(info)
    return Tensor(lu_mat), Tensor(piv)


def _complex_of(dt):
    return jnp.complex128 if dt == jnp.float64 else jnp.complex64


def eig(x, name=None):
    """paddle.linalg.eig — general eigendecomposition.  XLA has no TPU
    lowering for nonsymmetric eig (the reference's eig_op is CPU-only too):
    eager calls run numpy on host; traced calls go through
    jax.pure_callback."""
    import numpy as np
    x = ensure_tensor(x)
    a = x._data
    cdt = _complex_of(a.dtype)

    def host_eig(m):
        w, v = np.linalg.eig(np.asarray(m))
        return w.astype(cdt), v.astype(cdt)

    if isinstance(a, jax.core.Tracer):
        w_shape = jax.ShapeDtypeStruct(a.shape[:-1], cdt)
        v_shape = jax.ShapeDtypeStruct(a.shape, cdt)
        w, v = jax.pure_callback(host_eig, (w_shape, v_shape), a)
    else:
        # complex results stay on the host, where numpy computed them
        # (a TPU holds no complex128)
        cpu = jax.devices("cpu")[0]
        w, v = host_eig(a)
        w, v = jax.device_put(w, cpu), jax.device_put(v, cpu)
    return Tensor(w), Tensor(v)


def eigvals(x, name=None):
    import numpy as np
    x = ensure_tensor(x)
    a = x._data
    cdt = _complex_of(a.dtype)
    host = lambda m: np.linalg.eigvals(np.asarray(m)).astype(cdt)
    if isinstance(a, jax.core.Tracer):
        w = jax.pure_callback(
            host, jax.ShapeDtypeStruct(a.shape[:-1], cdt), a)
    else:
        w = jax.device_put(host(a), jax.devices("cpu")[0])
    return Tensor(w)
