"""Sparse NDArray storage types — row_sparse and csr — and their ops.

Reference: python/mxnet/ndarray/sparse.py (RowSparseNDArray:780,
CSRNDArray:998), include/mxnet/ndarray.h:82-87 (kRowSparseStorage,
kCSRStorage, aux tensors), and the sparse op family in
src/operator/tensor/: cast_storage-inl.h, sparse_retain-inl.h,
square_sum-inl.h, dot-inl.h (csr×dense / csrᵀ×dense → row_sparse).

TPU-native stance (SURVEY.md §7 hard-part 4): XLA has no native sparse
tensors, so these are *structured dense* containers — data + index aux
arrays, exactly the reference's aux-tensor layout. The compute lowerings
are gather/segment-sum formulations that XLA schedules well (and that
keep the FLOPs proportional to nnz, not to the dense shape):

- ``dot(csr, dense)``       → one gather + segment_sum over nnz
- ``dot(csrᵀ, dense)``      → scatter-add keyed by column → row_sparse
- ``sparse_retain``         → membership mask + gather
- ``square_sum``            → row-sparse-aware reduction
- ``elemwise_add(rsp,rsp)`` → index-union merge

Storage-type inference follows the reference's FInferStorageType tables:
outputs carry the stype the reference's op would produce.
"""
import numpy as np

import jax.numpy as jnp

from ..context import current_context
from .ndarray import NDArray, _build, array as _dense_array

__all__ = ['RowSparseNDArray', 'CSRNDArray', 'row_sparse_array', 'csr_matrix',
           'BaseSparseNDArray', 'cast_storage', 'retain', 'sparse_retain',
           'dot', 'square_sum', 'add', 'zeros', 'empty', 'array']


class BaseSparseNDArray:
    def __init__(self, shape, ctx=None, dtype='float32'):
        self._shape = tuple(shape)
        self._ctx = ctx or current_context()
        self._dtype = np.dtype(dtype) if dtype != 'bfloat16' else dtype

    @property
    def shape(self):
        return self._shape

    @property
    def size(self):
        return int(np.prod(self._shape))

    @property
    def dtype(self):
        return self._dtype

    @property
    def context(self):
        return self._ctx

    ctx = context

    def asnumpy(self):
        return self.tostype('default').asnumpy()

    def wait_to_read(self):
        pass

    def __repr__(self):
        return '<%s %s @%s>' % (type(self).__name__,
                                'x'.join(map(str, self._shape)), self._ctx)

    # dense-fallback arithmetic (reference elemwise ops accept
    # dense/sparse mixes and emit dense): subclasses override the cases
    # that stay sparse (scalar mul on row_sparse, rsp+rsp add)
    def _dense(self, other):
        return other.tostype('default') \
            if isinstance(other, BaseSparseNDArray) else other

    def __sub__(self, other):
        return self.tostype('default') - self._dense(other)

    def __rsub__(self, other):
        return self._dense(other) - self.tostype('default')

    def __truediv__(self, other):
        return self.tostype('default') / self._dense(other)

    def __rtruediv__(self, other):
        return self._dense(other) / self.tostype('default')

    def __neg__(self):
        return self * -1.0

    def __add__(self, other):
        return self.tostype('default') + self._dense(other)

    __radd__ = __add__

    def __mul__(self, other):
        return self.tostype('default') * self._dense(other)

    __rmul__ = __mul__


def _scatter_into_zeros(values, shape, index):
    """Zeros of `shape` with `values` at `index`, built where `values`
    live: jnp.zeros and .at[] alone start on the process's default
    device, which is not the context's on a machine with a chip."""
    def make():
        return jnp.zeros(shape, values.dtype).at[index].set(values)
    return _build(values.sharding, make)


class RowSparseNDArray(BaseSparseNDArray):
    """rows `indices` hold `data`; all other rows are zero
    (reference sparse.py:780, aux layout ndarray.h:82-87)."""

    stype = 'row_sparse'

    def __init__(self, data, indices, shape, ctx=None):
        super().__init__(shape, ctx, data.dtype)
        self.data = data          # NDArray (nnz_rows, *shape[1:])
        self.indices = indices    # NDArray int64 (nnz_rows,)

    def tostype(self, stype):
        if stype == 'row_sparse':
            return self
        if stype != 'default':
            raise ValueError('cast from row_sparse to %s is not supported'
                             % stype)
        return NDArray(_scatter_into_zeros(
            self.data._data, self._shape,
            (self.indices._data.astype(jnp.int32),)), self._ctx)

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = self.tostype('default')._data
            return other
        return RowSparseNDArray(self.data.copy(), self.indices.copy(),
                                self._shape, other)

    def copy(self):
        return RowSparseNDArray(self.data.copy(), self.indices.copy(),
                                self._shape, self._ctx)

    def retain(self, row_ids):
        return retain(self, row_ids)

    def __add__(self, other):
        if isinstance(other, RowSparseNDArray):
            return add(self, other)
        return self.tostype('default') + self._dense(other)

    __radd__ = __add__

    def __mul__(self, other):
        if np.isscalar(other):
            return RowSparseNDArray(self.data * other, self.indices,
                                    self._shape, self._ctx)
        return self.tostype('default') * self._dense(other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        if np.isscalar(other):
            return RowSparseNDArray(self.data / other, self.indices,
                                    self._shape, self._ctx)
        return self.tostype('default') / self._dense(other)

    def __sub__(self, other):
        if isinstance(other, RowSparseNDArray):
            return add(self, other * -1.0)
        return self.tostype('default') - self._dense(other)


class CSRNDArray(BaseSparseNDArray):
    """Compressed sparse row matrix (reference sparse.py:998)."""

    stype = 'csr'

    def __init__(self, data, indptr, indices, shape, ctx=None):
        super().__init__(shape, ctx, data.dtype)
        self.data = data
        self.indptr = indptr
        self.indices = indices

    def tostype(self, stype):
        if stype == 'csr':
            return self
        if stype == 'row_sparse':
            # reference cast_storage supports csr -> rsp via dense rows
            return row_sparse_array(self.tostype('default'), ctx=self._ctx,
                                    dtype=self.data.asnumpy().dtype)
        if stype != 'default':
            raise ValueError(stype)
        return NDArray(_scatter_into_zeros(
            self.data._data, self._shape,
            (self._row_ids(), self.indices._data.astype(jnp.int32))),
            self._ctx)

    def _row_ids(self):
        """nnz-length row id per value, from indptr (host-side: aux
        indices are concrete metadata, exactly like the reference's
        aux_data on CPU)."""
        ptr = self.indptr.asnumpy().astype(np.int64)
        return _build(self.data._data.sharding,
                      np.repeat(np.arange(len(ptr) - 1), np.diff(ptr)),
                      np.int32)

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = self.tostype('default')._data
            return other
        return CSRNDArray(self.data.copy(), self.indptr.copy(),
                          self.indices.copy(), self._shape, other)

    def copy(self):
        return CSRNDArray(self.data.copy(), self.indptr.copy(),
                          self.indices.copy(), self._shape, self._ctx)

    def __getitem__(self, key):
        """Row slicing (reference sparse.py CSRNDArray.__getitem__)."""
        if isinstance(key, int):
            key = slice(key, key + 1)
        start, stop, step = key.indices(self._shape[0])
        if step != 1:
            raise ValueError('CSR slicing requires step 1')
        ptr = self.indptr.asnumpy().astype(np.int64)
        lo, hi = int(ptr[start]), int(ptr[stop])
        return CSRNDArray(
            _dense_array(self.data.asnumpy()[lo:hi], self._ctx),
            _dense_array(ptr[start:stop + 1] - lo, self._ctx, dtype='int64'),
            _dense_array(self.indices.asnumpy()[lo:hi], self._ctx,
                         dtype='int64'),
            (stop - start, self._shape[1]), self._ctx)


# ---------------------------------------------------------------------------
# Constructors
# ---------------------------------------------------------------------------

def row_sparse_array(arg1, shape=None, ctx=None, dtype='float32'):
    """Reference sparse.py row_sparse_array: from (data, indices) or dense."""
    if isinstance(arg1, tuple) and len(arg1) == 2:
        data, indices = arg1
        data = data if isinstance(data, NDArray) else \
            _dense_array(np.asarray(data, dtype=dtype), ctx)
        indices = indices if isinstance(indices, NDArray) else \
            _dense_array(np.asarray(indices, dtype=np.int64), ctx,
                         dtype='int64')
        if shape is None:
            nrows = int(indices.asnumpy().max()) + 1 if indices.size else 0
            shape = (nrows,) + data.shape[1:]
        return RowSparseNDArray(data, indices, shape, ctx)
    dense = np.asarray(arg1.asnumpy() if isinstance(arg1, NDArray) else arg1,
                       dtype=dtype)
    nz = np.where(np.any(dense.reshape(dense.shape[0], -1) != 0, axis=1))[0]
    return RowSparseNDArray(
        _dense_array(dense[nz], ctx),
        _dense_array(nz.astype(np.int64), ctx, dtype='int64'),
        dense.shape, ctx)


def csr_matrix(arg1, shape=None, ctx=None, dtype='float32'):
    if isinstance(arg1, tuple) and len(arg1) == 3:
        data, indices, indptr = arg1
        data = data if isinstance(data, NDArray) else \
            _dense_array(np.asarray(data, dtype=dtype), ctx)
        indices = indices if isinstance(indices, NDArray) else \
            _dense_array(np.asarray(indices, dtype=np.int64), ctx,
                         dtype='int64')
        indptr = indptr if isinstance(indptr, NDArray) else \
            _dense_array(np.asarray(indptr, dtype=np.int64), ctx,
                         dtype='int64')
        return CSRNDArray(data, indptr, indices, shape, ctx)
    import scipy.sparse as sp
    dense = np.asarray(arg1.asnumpy() if isinstance(arg1, NDArray) else arg1,
                       dtype=dtype)
    m = sp.csr_matrix(dense)
    return CSRNDArray(
        _dense_array(m.data, ctx),
        _dense_array(m.indptr.astype(np.int64), ctx, dtype='int64'),
        _dense_array(m.indices.astype(np.int64), ctx, dtype='int64'),
        dense.shape, ctx)


def zeros(stype, shape, ctx=None, dtype='float32'):
    """Reference sparse.py zeros — an all-zero sparse array (no stored
    values)."""
    if stype == 'row_sparse':
        return RowSparseNDArray(
            _dense_array(np.zeros((0,) + tuple(shape[1:]), dtype), ctx),
            _dense_array(np.zeros((0,), np.int64), ctx, dtype='int64'),
            shape, ctx)
    if stype == 'csr':
        return CSRNDArray(
            _dense_array(np.zeros((0,), dtype), ctx),
            _dense_array(np.zeros((shape[0] + 1,), np.int64), ctx,
                         dtype='int64'),
            _dense_array(np.zeros((0,), np.int64), ctx, dtype='int64'),
            shape, ctx)
    from . import zeros as dense_zeros
    return dense_zeros(shape, ctx, dtype)


empty = zeros


def array(source, ctx=None, dtype='float32'):
    """Reference sparse.py array — sparse-in → same-stype copy."""
    if isinstance(source, RowSparseNDArray):
        return source.copy()
    if isinstance(source, CSRNDArray):
        return source.copy()
    import scipy.sparse as sp
    if sp.issparse(source):
        m = source.tocsr()
        return csr_matrix((m.data, m.indices, m.indptr), shape=m.shape,
                          ctx=ctx, dtype=dtype)
    raise ValueError('use mx.nd.array for dense sources')


# ---------------------------------------------------------------------------
# Sparse operators (reference src/operator/tensor/)
# ---------------------------------------------------------------------------

def cast_storage(arr, stype):
    """Reference cast_storage-inl.h: dense↔row_sparse↔csr."""
    if isinstance(arr, BaseSparseNDArray):
        return arr.tostype(stype)
    if stype == 'default':
        return arr.copy()
    if stype == 'row_sparse':
        return row_sparse_array(arr, ctx=arr.context,
                                dtype=arr.asnumpy().dtype)
    if stype == 'csr':
        if len(arr.shape) != 2:
            raise ValueError('csr requires a 2-d array')
        return csr_matrix(arr, ctx=arr.context, dtype=arr.asnumpy().dtype)
    raise ValueError('unknown storage type %r' % (stype,))


def retain(rsp, row_ids):
    """Reference sparse_retain op (tensor/sparse_retain-inl.h): keep only
    the requested rows of a row_sparse array (missing rows stay absent)."""
    if not isinstance(rsp, RowSparseNDArray):
        raise TypeError('sparse_retain expects a RowSparseNDArray')
    want = np.unique(np.asarray(
        row_ids.asnumpy() if isinstance(row_ids, NDArray) else row_ids
    ).astype(np.int64))
    have = rsp.indices.asnumpy().astype(np.int64)
    mask = np.isin(have, want)
    sel = np.flatnonzero(mask)
    data = rsp.data.asnumpy()[sel] if len(sel) else \
        np.zeros((0,) + rsp.shape[1:], dtype=rsp.data.asnumpy().dtype)
    return RowSparseNDArray(
        _dense_array(data, rsp._ctx),
        _dense_array(have[mask], rsp._ctx, dtype='int64'),
        rsp.shape, rsp._ctx)


sparse_retain = retain


def dot(lhs, rhs, transpose_a=False, transpose_b=False):
    """Sparse dot (reference tensor/dot-inl.h FInferStorageType):
    dot(csr, dense) → dense; dot(csrᵀ, dense) → row_sparse."""
    if transpose_b:
        raise NotImplementedError('transpose_b with sparse inputs '
                                  '(unsupported in the reference too)')
    if isinstance(lhs, CSRNDArray):
        rows = lhs._row_ids()
        cols = lhs.indices._data.astype(jnp.int32)
        vals = lhs.data._data
        dense_rhs = (rhs.tostype('default')
                     if isinstance(rhs, BaseSparseNDArray) else rhs)._data
        if not transpose_a:
            # out[i] = Σ_nnz vals * rhs[cols] grouped by row — one gather
            # + segment-sum, FLOPs ∝ nnz
            import jax
            contrib = vals[:, None] * dense_rhs[cols]       # [nnz, N]
            out = jax.ops.segment_sum(contrib, rows,
                                      num_segments=lhs.shape[0])
            return NDArray(out.astype(dense_rhs.dtype), lhs._ctx)
        # csrᵀ × dense: scatter by column index → row_sparse output
        import jax
        contrib = vals[:, None] * dense_rhs[rows]           # [nnz, N]
        out = jax.ops.segment_sum(contrib, cols,
                                  num_segments=lhs.shape[1])
        nz = np.unique(lhs.indices.asnumpy().astype(np.int64))
        return RowSparseNDArray(
            NDArray(out[_build(out.sharding, nz, np.int32)], lhs._ctx),
            _dense_array(nz, lhs._ctx, dtype='int64'),
            (lhs.shape[1], dense_rhs.shape[1]), lhs._ctx)
    if isinstance(rhs, BaseSparseNDArray) or isinstance(lhs,
                                                        BaseSparseNDArray):
        lhs_d = lhs.tostype('default') if isinstance(
            lhs, BaseSparseNDArray) else lhs
        rhs_d = rhs.tostype('default') if isinstance(
            rhs, BaseSparseNDArray) else rhs
        from . import dot as dense_dot
        return dense_dot(lhs_d, rhs_d, transpose_a=transpose_a)
    from . import dot as dense_dot
    return dense_dot(lhs, rhs, transpose_a=transpose_a,
                     transpose_b=transpose_b)


def square_sum(rsp, axis=None, keepdims=False):
    """Reference square_sum-inl.h: Σ x² over a row_sparse array without
    densifying — axis=1 keeps the row structure (row_sparse out)."""
    if not isinstance(rsp, RowSparseNDArray):
        raise TypeError('square_sum expects a RowSparseNDArray')
    sq = rsp.data._data.astype(jnp.float32) ** 2
    if axis is None:
        out = sq.sum()
        return NDArray(out.reshape((1,) * len(rsp.shape)) if keepdims
                       else out, rsp._ctx)
    axis = int(axis) % len(rsp.shape)
    if axis == 1:
        row_sums = sq.sum(axis=tuple(range(1, sq.ndim)))
        if keepdims:
            data = NDArray(row_sums[:, None], rsp._ctx)
            return RowSparseNDArray(data, rsp.indices,
                                    (rsp.shape[0], 1), rsp._ctx)
        return NDArray(_scatter_into_zeros(
            row_sums, (rsp.shape[0],),
            (rsp.indices._data.astype(jnp.int32),)), rsp._ctx)
    # axis == 0: reduce over rows → dense row vector
    out = sq.sum(axis=0)
    return NDArray(out[None] if keepdims else out, rsp._ctx)


def add(a, b):
    """elemwise_add(rsp, rsp) → rsp via index-union merge (reference
    elemwise_binary_op_basic.cc sparse kernels)."""
    if not (isinstance(a, RowSparseNDArray) and
            isinstance(b, RowSparseNDArray)):
        a_d = a.tostype('default') if isinstance(a, BaseSparseNDArray) else a
        b_d = b.tostype('default') if isinstance(b, BaseSparseNDArray) else b
        return a_d + b_d
    assert a.shape == b.shape, (a.shape, b.shape)
    ia = a.indices.asnumpy().astype(np.int64)
    ib = b.indices.asnumpy().astype(np.int64)
    union = np.union1d(ia, ib)
    pos = {r: i for i, r in enumerate(union)}
    out = np.zeros((len(union),) + a.shape[1:], a.data.asnumpy().dtype)
    da, db = a.data.asnumpy(), b.data.asnumpy()
    for j, r in enumerate(ia):
        out[pos[r]] += da[j]
    for j, r in enumerate(ib):
        out[pos[r]] += db[j]
    return RowSparseNDArray(_dense_array(out, a._ctx),
                            _dense_array(union, a._ctx, dtype='int64'),
                            a.shape, a._ctx)
