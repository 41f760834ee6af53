"""Data iterators.

Reference: python/mxnet/io.py (932 LoC: DataDesc/DataBatch, DataIter,
NDArrayIter:516, PrefetchingIter:343, ResizeIter, MXDataIter) + src/io/
(MNISTIter, CSVIter, ImageRecordIter family — the C++ decode→augment→batch
→prefetch pipeline).

TPU-native: host-side pipelines feed device arrays; the PrefetchingIter
double-buffers with a background thread (the engine-façade host worker),
overlapping host IO with device compute like the reference's
PrefetcherIter (src/io/iter_prefetcher.h:46).
"""
from collections import namedtuple
import os
import struct
import gzip
import threading
import time

import numpy as np

from .. import random as _random
from .. import telemetry as _tele

from ..ndarray import NDArray, array
from ..base import MXNetError

__all__ = ['DataDesc', 'DataBatch', 'DataIter', 'NDArrayIter', 'CSVIter',
           'MNISTIter', 'ResizeIter', 'PrefetchingIter', 'ImageRecordIter',
           'ImageDetRecordIter', 'LibSVMIter', 'MXDataIter', 'auto_shard']


def auto_shard():
    """``{'num_parts': P, 'part_index': i}`` derived from the LIVE
    process set — construct data iterators with ``**mx.io.auto_shard()``
    and an elastic job keeps every example covered exactly once however
    many hosts survive: a supervisor relaunch onto fewer hosts
    re-derives the shard ranges from the smaller set instead of leaving
    the dead host's shard orphaned (module/checkpointing.py remaps the
    resumed iterator cursor to match). Prefers the launcher env
    (MXTPU_NUM_HOSTS / MXTPU_HOST_ID — tools/launch.py exports both);
    falls back to jax's process set when the env is silent but
    jax.distributed is up."""
    n, i = 1, 0
    try:
        from ..config import flags
        flags.reload('MXTPU_NUM_HOSTS')
        flags.reload('MXTPU_HOST_ID')
        n = int(flags.get('MXTPU_NUM_HOSTS'))
        i = int(flags.get('MXTPU_HOST_ID'))
    except Exception:  # noqa: BLE001 — stripped builds without the flags
        pass
    if n <= 1:
        try:
            import jax
            n = int(jax.process_count())
            i = int(jax.process_index())
        except Exception:  # noqa: BLE001 — backend not up yet
            pass
    n = max(1, n)
    return {'num_parts': n, 'part_index': i % n}


class DataDesc(namedtuple('DataDesc', ['name', 'shape'])):
    """Reference io.py DataDesc (name, shape, dtype, layout)."""

    def __new__(cls, name, shape, dtype=np.float32, layout='NCHW'):
        ret = super().__new__(cls, name, shape)
        ret.dtype = dtype
        ret.layout = layout
        return ret

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find('N')


class DataBatch:
    """Reference io.py DataBatch."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        if data is not None:
            assert isinstance(data, (list, tuple)), 'Data must be list of NDArrays'
        if label is not None:
            assert isinstance(label, (list, tuple)), 'Label must be list of NDArrays'
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Reference io.py:176."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            _tele.counter('io.batches').inc()
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()


def _init_data(data, allow_empty, default_name):
    """Reference io.py:476 _init_data."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {('_%d_%s' % (i, default_name)): d
                    for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError('Input must be NDArray, numpy.ndarray, a list of them '
                        'or dict with them as values')
    out = []
    for k, v in data.items():
        if not isinstance(v, NDArray):
            v = array(np.asarray(v))
        out.append((k, v))
    return out


class NDArrayIter(DataIter):
    """In-memory iterator with pad/discard/roll_over (reference io.py:516)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle='pad', data_name='data',
                 label_name='softmax_label'):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)

        self.idx = np.arange(self.data[0][1].shape[0])
        if shuffle:
            _random.host_rng().shuffle(self.idx)
        self._shuffle = shuffle

        if last_batch_handle == 'discard':
            new_n = self.data[0][1].shape[0] - self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]

        self.data_list = [x[1] for x in self.data] + [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, \
            'batch_size needs to be smaller than data size.'
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle
        # numpy staging for fast fancy-indexing
        self._np_data = [x[1].asnumpy() for x in self.data]
        self._np_label = [x[1].asnumpy() for x in self.label]

    @property
    def provide_data(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.data]

    @property
    def provide_label(self):
        return [DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])),
                         v.dtype) for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self._shuffle:
            _random.host_rng().shuffle(self.idx)
        if self.last_batch_handle == 'roll_over' and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def next(self):
        if self.iter_next():
            _tele.counter('io.batches').inc()
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=None,
                             provide_data=self.provide_data,
                             provide_label=self.provide_label)
        raise StopIteration

    def _getdata(self, arrays):
        assert self.cursor < self.num_data, 'DataIter needs reset.'
        if self.cursor + self.batch_size <= self.num_data:
            sel = self.idx[self.cursor:self.cursor + self.batch_size]
        else:
            pad = self.batch_size - self.num_data + self.cursor
            sel = np.concatenate([self.idx[self.cursor:], self.idx[:pad]])
        return [array(a[sel]) for a in arrays]

    def getdata(self):
        return self._getdata(self._np_data)

    def getlabel(self):
        return self._getdata(self._np_label)

    def getpad(self):
        if self.last_batch_handle == 'pad' and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class ResizeIter(DataIter):
    """Resize the epoch length of an iterator (reference io.py:288)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Thread double-buffering (reference io.py:343 / iter_prefetcher.h)."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.data_ready = [threading.Event() for _ in range(self.n_iter)]
        self.data_taken = [threading.Event() for _ in range(self.n_iter)]
        for e in self.data_taken:
            e.set()
        self.started = True
        self.current_batch = [None for _ in range(self.n_iter)]
        self.next_batch = [None for _ in range(self.n_iter)]

        def prefetch_func(self, i):
            while True:
                self.data_taken[i].wait()
                if not self.started:
                    break
                try:
                    self.next_batch[i] = self.iters[i].next()
                except StopIteration:
                    self.next_batch[i] = None
                self.data_taken[i].clear()
                self.data_ready[i].set()

        self.prefetch_threads = [
            threading.Thread(target=prefetch_func, args=[self, i], daemon=True)
            for i in range(self.n_iter)]
        for thread in self.prefetch_threads:
            thread.start()

    def __del__(self):
        self.started = False
        for e in self.data_taken:
            e.set()

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[DataDesc(r[x.name], x.shape, x.dtype)
                     if isinstance(x, DataDesc) else DataDesc(*x)
                     for x in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        for e in self.data_ready:
            e.wait()
        for i in self.iters:
            i.reset()
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()

    def iter_next(self):
        if _tele.enabled():
            # how long the consumer stalled on the producer thread(s) —
            # the "is the input pipeline the bottleneck?" histogram
            t0 = time.time()
            for e in self.data_ready:
                e.wait()
            _tele.histogram('io.prefetch_wait').observe(
                (time.time() - t0) * 1e3)
        else:
            for e in self.data_ready:
                e.wait()
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, 'Number of entry mismatches between iterators'
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                'Number of entry mismatches between iterators'
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad, self.next_batch[0].index,
            provide_data=self.provide_data, provide_label=self.provide_label)
        for e in self.data_ready:
            e.clear()
        for e in self.data_taken:
            e.set()
        return True

    def next(self):
        # no io.batches inc here: the producer thread's inner
        # iters[i].next() calls already count each batch once
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _read_mnist_images(path):
    opener = gzip.open if path.endswith('.gz') else open
    with opener(path, 'rb') as f:
        magic, num, rows, cols = struct.unpack('>IIII', f.read(16))
        assert magic == 2051
        return np.frombuffer(f.read(), dtype=np.uint8).reshape(num, rows, cols)


def _read_mnist_labels(path):
    opener = gzip.open if path.endswith('.gz') else open
    with opener(path, 'rb') as f:
        magic, num = struct.unpack('>II', f.read(8))
        assert magic == 2049
        return np.frombuffer(f.read(), dtype=np.uint8)


class MNISTIter(NDArrayIter):
    """Reference src/io/iter_mnist.cc — reads idx-format files.

    If the files are absent, generates a deterministic synthetic set with
    class-separable structure so training/convergence tests run hermetically.
    """

    def __init__(self, image='train-images-idx3-ubyte', label='train-labels-idx1-ubyte',
                 batch_size=128, shuffle=True, flat=False, silent=False,
                 seed=0, num_parts=1, part_index=0, input_shape=None, **kwargs):
        if os.path.exists(image) or os.path.exists(image + '.gz'):
            img_path = image if os.path.exists(image) else image + '.gz'
            lab_path = label if os.path.exists(label) else label + '.gz'
            images = _read_mnist_images(img_path).astype(np.float32) / 255.0
            labels = _read_mnist_labels(lab_path).astype(np.float32)
        else:
            images, labels = synthetic_mnist(12000 if 'train' in image else 2000,
                                             seed=seed)
        # the full (pre-shard) set is kept ONLY for genuinely sharded
        # construction, so an elastic re-balance (telemetry/cluster.py
        # apply_shard_shift) can re-slice it: set_shard(j) rebuilds
        # this iterator on shard j of num_parts. Unsharded iterators
        # (num_parts=1 — elastic has nothing to rotate and disables
        # itself) don't pay the extra retention
        self._shard_full = (images, labels) if num_parts > 1 else None
        self._shard_args = dict(batch_size=batch_size, shuffle=shuffle,
                                flat=flat, seed=seed)
        self._num_parts = int(num_parts)
        self._part_index = int(part_index)
        self._shard_init(images, labels)

    def _shard_init(self, images, labels):
        a = self._shard_args
        if self._num_parts > 1:
            images = images[self._part_index::self._num_parts]
            labels = labels[self._part_index::self._num_parts]
        if a['flat']:
            images = images.reshape(images.shape[0], -1)
        else:
            images = images.reshape(images.shape[0], 1, 28, 28)
        if a['shuffle']:
            # reference iter_mnist.cc shuffles ONCE at init with `seed`;
            # reset() rewinds to the SAME order. Scripts rely on this:
            # e.g. module/mnist_mlp.py aligns predict(merge_batches=False)
            # outputs against a second pass of the iterator by index.
            perm = np.random.RandomState(a['seed']).permutation(len(labels))
            images, labels = images[perm], labels[perm]
        super().__init__(images, labels, batch_size=a['batch_size'],
                         shuffle=False, last_batch_handle='discard',
                         label_name='softmax_label')

    def shard_info(self):
        """(num_parts, part_index) — the elastic-input shard protocol."""
        return self._num_parts, self._part_index

    def set_shard(self, part_index):
        """Re-slice this iterator onto shard ``part_index`` of the same
        ``num_parts`` partition (elastic input re-balancing; the rebuilt
        order is deterministic from the original seed). Takes effect
        immediately — callers apply it at an epoch boundary. A no-op on
        unsharded iterators (num_parts=1: there is only shard 0)."""
        if self._shard_full is None:
            return
        self._part_index = int(part_index) % max(1, self._num_parts)
        images, labels = self._shard_full
        self._shard_init(images, labels)


def synthetic_mnist(n, seed=0):
    """Class-separable synthetic digits: 10 fixed random prototype images +
    noise. Linearly separable enough for LeNet/MLP convergence tests."""
    protos = np.random.RandomState(42).rand(10, 28, 28).astype(np.float32)
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 10, size=n)
    images = protos[labels] + 0.3 * rng.randn(n, 28, 28).astype(np.float32)
    return np.clip(images, 0, 1).astype(np.float32), labels.astype(np.float32)


class CSVIter(NDArrayIter):
    """Reference src/io/iter_csv.cc."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, data_name='data',
                 label_name='softmax_label', **kwargs):
        data = np.loadtxt(data_csv, delimiter=',', dtype=np.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=',', dtype=np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros(data.shape[0], dtype=np.float32)
        super().__init__(data, label, batch_size=batch_size,
                         last_batch_handle='pad' if round_batch else 'discard',
                         data_name=data_name, label_name=label_name)


class LibSVMIter(DataIter):
    """Reference src/io/iter_libsvm.cc — sparse libsvm text format.

    Each line: ``label [label...] idx:value idx:value ...`` (indices
    0-based like the reference's default). Batches come out as
    CSRNDArray data (the sparse path the reference feeds to sparse
    FullyConnected / linear models) with dense label arrays. An optional
    separate ``label_libsvm`` file provides multi-dim sparse labels,
    densified per batch.
    """

    def __init__(self, data_libsvm, data_shape, label_libsvm=None,
                 label_shape=None, batch_size=1, round_batch=True,
                 data_name='data', label_name='softmax_label', **kwargs):
        super().__init__(batch_size)
        self.data_shape = tuple(data_shape) if not isinstance(
            data_shape, int) else (data_shape,)
        ncol = int(np.prod(self.data_shape))
        rows, labels = self._parse(data_libsvm, ncol)
        self._csr = rows                     # scipy csr [N, ncol]
        if label_libsvm is not None:
            lab_ncol = int(np.prod(label_shape)) if label_shape else 1
            lab, _ = self._parse(label_libsvm, lab_ncol, labels_inline=False)
            self._labels = np.asarray(lab.todense(), np.float32)
        else:
            self._labels = np.asarray(labels, np.float32)
        self.num_data = self._csr.shape[0]
        if self.num_data < batch_size:
            raise ValueError('fewer rows (%d) than batch_size (%d)'
                             % (self.num_data, batch_size))
        self.round_batch = round_batch
        # naming matches the reference frontend: every C++-registered
        # iterator surfaces through MXDataIter whose defaults are
        # data_name='data', label_name='softmax_label' (python io.py:766)
        self.provide_data = [DataDesc(data_name,
                                      (batch_size,) + self.data_shape)]
        lshape = (batch_size,) if self._labels.ndim == 1 else \
            (batch_size,) + self._labels.shape[1:]
        self.provide_label = [DataDesc(label_name, lshape)]
        self.reset()

    @staticmethod
    def _parse(path, ncol, labels_inline=True):
        import scipy.sparse as sp
        data, indices, indptr, labels = [], [], [0], []
        with open(path) as f:
            for line in f:
                parts = line.split()
                if not parts:
                    continue
                i = 0
                if labels_inline:
                    labels.append(float(parts[0]))
                    i = 1
                for tok in parts[i:]:
                    idx, val = tok.split(':')
                    indices.append(int(idx))
                    data.append(float(val))
                indptr.append(len(data))
        mat = sp.csr_matrix(
            (np.asarray(data, np.float32),
             np.asarray(indices, np.int64), np.asarray(indptr, np.int64)),
            shape=(len(indptr) - 1, ncol))
        return mat, np.asarray(labels, np.float32)

    def reset(self):
        self._cursor = 0

    def iter_next(self):
        if self._cursor + self.batch_size <= self.num_data:
            return True
        if self.round_batch and self._cursor < self.num_data:
            return True
        return False

    def next(self):
        if not self.iter_next():
            raise StopIteration
        from ..ndarray.sparse import csr_matrix as _csr_nd
        start = self._cursor
        stop = start + self.batch_size
        pad = 0
        if stop <= self.num_data:
            sub = self._csr[start:stop]
            lab = self._labels[start:stop]
        else:  # wrap-around pad (reference round_batch semantics)
            pad = stop - self.num_data
            import scipy.sparse as sp
            sub = sp.vstack([self._csr[start:], self._csr[:pad]]).tocsr()
            lab = np.concatenate([self._labels[start:], self._labels[:pad]])
        self._cursor = stop
        data = _csr_nd((sub.data, sub.indices, sub.indptr),
                       shape=(self.batch_size,) + self.data_shape)
        from .. import ndarray as _nd
        return DataBatch(data=[data], label=[_nd.array(lab)], pad=pad,
                         index=None)

    def getpad(self):
        return 0


def _read_imgrec(path_imgrec, data_shape, scale, means, stds):
    """Shared RecordIO image loader: decode every record, normalize.

    Returns (data (N,C,H,W) float32, raw label list). Used by both
    ImageRecordIter and ImageDetRecordIter (reference shares this in
    ImageRecordIOParser)."""
    from ..recordio import MXRecordIO, unpack_img
    record = MXRecordIO(path_imgrec, 'r')
    images, labels = [], []
    while True:
        item = record.read()
        if item is None:
            break
        header, img = unpack_img(item, data_shape=tuple(data_shape))
        images.append(img)
        labels.append(header.label)
    record.close()
    if not images:
        raise ValueError('empty record file %s' % path_imgrec)
    data = np.stack(images).astype(np.float32) * scale
    mean = np.asarray(means, dtype=np.float32).reshape(3, 1, 1)
    std = np.asarray(stds, dtype=np.float32).reshape(3, 1, 1)
    if data.shape[1] == 3:
        data = (data - mean) / std
    return data, labels


class ImageRecordIter(DataIter):
    """Reference src/io/iter_image_recordio_2.cc — RecordIO image pipeline.

    Streaming (round 4): a framing-only offset scan at construction,
    then a producer thread + ``preprocess_threads`` decode/augment
    workers + a ``prefetch_buffer``-bounded batch queue
    (io/image_record.py). Memory is O(batch x prefetch), independent of
    dataset size; augmentation (rand_crop / rand_mirror / scale jitter
    / pad) is per-image, matching image_aug_default.cc.
    """

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, mean_r=0, mean_g=0, mean_b=0, std_r=1,
                 std_g=1, std_b=1, scale=1.0, rand_crop=False,
                 rand_mirror=False, preprocess_threads=4, round_batch=True,
                 prefetch_buffer=4, resize=-1, pad=0, fill_value=127,
                 max_random_scale=1.0, min_random_scale=1.0, num_parts=1,
                 part_index=0, data_name='data', label_name='softmax_label',
                 device_augment=None, host_crop=None, **kwargs):
        super().__init__(batch_size)
        from .image_record import StreamingImageRecordIter
        from ..config import flags
        self.data_shape = tuple(data_shape)
        self._data_name = data_name
        self._label_name = label_name
        self._label_width = label_width
        if device_augment is None:
            # opt-in for unmodified scripts: MXTPU_DEVICE_AUGMENT=1
            device_augment = flags.get('MXTPU_DEVICE_AUGMENT')
        self._device_augment = bool(int(device_augment or 0))
        if host_crop is None:
            host_crop = flags.get('MXTPU_HOST_CROP')
        self._host_crop = bool(int(host_crop or 0)) and self._device_augment
        self._aug_params = dict(
            scale=float(scale), mean=(mean_r, mean_g, mean_b),
            std=(std_r, std_g, std_b), rand_crop=bool(int(rand_crop)),
            rand_mirror=bool(int(rand_mirror)))
        self._aug_fn = None
        self._defer_aug = False
        self._stream = StreamingImageRecordIter(
            path_imgrec, self.data_shape, batch_size,
            label_width=label_width, shuffle=shuffle,
            mean=(mean_r, mean_g, mean_b), std=(std_r, std_g, std_b),
            scale=scale, rand_crop=rand_crop, rand_mirror=rand_mirror,
            preprocess_threads=preprocess_threads,
            prefetch_buffer=prefetch_buffer, round_batch=round_batch,
            resize=resize, pad=pad, fill_value=fill_value,
            max_random_scale=max_random_scale,
            min_random_scale=min_random_scale,
            num_parts=num_parts, part_index=part_index, aug_kwargs=kwargs,
            device_augment=self._device_augment, host_crop=self._host_crop)
        self._pending = None
        self._exhausted = False

    @property
    def provide_data(self):
        return [DataDesc(self._data_name,
                         (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self._label_width == 1 else \
            (self.batch_size, self._label_width)
        return [DataDesc(self._label_name, shape)]

    def reset(self):
        self._stream.start_epoch()
        self._pending = None
        self._exhausted = False

    def shard_info(self):
        """(num_parts, part_index) — the elastic-input shard protocol
        (telemetry/cluster.py apply_shard_shift)."""
        return self._stream.num_parts, self._stream.part_index

    def set_shard(self, part_index):
        """Move this iterator onto another shard of the same partition;
        applies at the next reset() (epoch boundary)."""
        self._stream.set_shard(part_index)

    def next(self):
        if self._pending is not None:
            batch, self._pending = self._pending, None
            return batch
        if self._exhausted:
            raise StopIteration
        item = self._stream.next_batch()
        if item is None:
            self._exhausted = True
            raise StopIteration
        _tele.counter('io.batches').inc()
        data, label, pad = item
        from .. import ndarray as _nd
        if self._device_augment and self._defer_aug:
            # deferred mode (enabled by the fused fit loop via
            # defer_device_aug): hand over the raw uint8 batch AND its
            # label HOST-resident; the consumer stacks a whole window
            # and crosses to the device in ONE transfer, tracing
            # device_aug_pure() INSIDE its compiled program. Per-batch
            # device calls each cost a host dispatch on the step's
            # critical path — defer mode leaves zero of them
            import jax
            from ..context import current_context
            from ..ndarray.ndarray import _build
            ctx = current_context()
            try:
                host = jax.local_devices(backend='cpu')[0]
            except RuntimeError:   # no cpu backend: the context's device
                host = ctx.jax_device()

            def host_nd(a):
                # one host copy per batch, into memory the cpu backend
                # adopts as the array's own (it would else take its own
                # behind the call); the window's stack reads it there
                return NDArray(_build(host, a, a.dtype), ctx)

            return DataBatch(data=[host_nd(data)], label=[host_nd(label)],
                             pad=pad, index=None,
                             provide_data=self.provide_data,
                             provide_label=self.provide_label)
        elif self._device_augment:
            data_nd = self._apply_device_aug(data)
        else:
            data_nd = _nd.array(data)
        return DataBatch(data=[data_nd], label=[_nd.array(label)],
                         pad=pad, index=None,
                         provide_data=self.provide_data,
                         provide_label=self.provide_label)

    def _apply_device_aug(self, data_u8):
        """One jitted device call: (B, S, S, C) uint8 → augmented
        (B, C, H, W) float32 (crop / mirror / scale-mean-std). The
        uint8 upload is 4x smaller than the host-augmented f32 batch,
        and the float math rides the accelerator instead of the
        decode-bound host cores (reference inline-augment role:
        src/io/iter_image_recordio_2.cc:122-130)."""
        import jax
        from .. import random as _random
        from ..ndarray.ndarray import from_jax
        from ..context import current_context
        if self._aug_fn is None:
            self._aug_fn = jax.jit(self.device_aug_pure())
        ctx = current_context()
        dev = jax.device_put(np.ascontiguousarray(data_u8),
                             ctx.jax_device())
        return from_jax(self._aug_fn(dev, _random.next_key()), ctx)

    def device_aug_pure(self):
        """The device-augment math as a PURE jax function
        ``(uint8 (B, Sh, Sw, C'), key) -> float32 (B, C, H, W)`` —
        source dims read off the traced batch, so one function serves
        any record geometry. Eager mode jits it per batch
        (_apply_device_aug); the fused fit loop traces it inside its
        window program instead (defer_device_aug), which removes the
        per-batch dispatch entirely."""
        import jax
        import jax.numpy as jnp
        C, H, W = self.data_shape
        p = self._aug_params
        # slice to the target channel count (grayscale data_shape
        # uses only the first channel's mean/std, like the host LUT)
        mean_c = tuple(p['mean'][:C])
        std_c = tuple(p['std'][:C])
        scale_v = float(p['scale'])
        rand_crop, rand_mirror = p['rand_crop'], p['rand_mirror']
        pre_cropped = self._host_crop

        def aug(batch, key):
            B = batch.shape[0]
            # source may be non-square (uniform raw records): crop
            # offsets range over each axis independently
            Sh, Sw = int(batch.shape[1]), int(batch.shape[2])
            mean = jnp.asarray(mean_c, jnp.float32)[:, None, None]
            std = jnp.asarray(std_c, jnp.float32)[:, None, None]
            ky, kx, kf = jax.random.split(key, 3)
            if pre_cropped:
                # host-crop mode: workers already cropped to (H, W) —
                # only mirror + normalize ride the device
                imgs = batch
            else:
                if rand_crop and (Sh > H or Sw > W):
                    ys = jax.random.randint(ky, (B,), 0, Sh - H + 1)
                    xs = jax.random.randint(kx, (B,), 0, Sw - W + 1)
                else:
                    ys = jnp.full((B,), (Sh - H) // 2, jnp.int32)
                    xs = jnp.full((B,), (Sw - W) // 2, jnp.int32)
                crop = lambda im, y, x: jax.lax.dynamic_slice(  # noqa: E731
                    im, (y, x, 0), (H, W, C))
                imgs = jax.vmap(crop)(batch, ys, xs)     # (B,H,W,C) u8
            if rand_mirror:
                coins = jax.random.uniform(kf, (B,)) < 0.5
                imgs = jnp.where(coins[:, None, None, None],
                                 imgs[:, :, ::-1, :], imgs)
            chw = imgs.transpose(0, 3, 1, 2).astype(jnp.float32)
            return (chw * jnp.float32(scale_v) - mean) / std

        return aug

    def device_aug_signature(self):
        """Hashable description of the augmentation MATH a consumer
        bakes into a compiled program (fused-fit defer mode): two
        iterators agreeing on this signature produce identical
        device_aug_pure functions, so compiled windows may be shared;
        any difference (mean/std/scale/rand flags/target shape) must
        compile a fresh window."""
        p = self._aug_params
        return ('image-record-aug', tuple(self.data_shape), p['scale'],
                tuple(p['mean']), tuple(p['std']),
                p['rand_crop'], p['rand_mirror'], self._host_crop)

    def defer_device_aug(self, on):
        """Switch deferred-augment mode (the compiled-window loops'
        internal protocol — module/fused_fit.py today): when on,
        next() returns RAW uint8 host batches and the consumer must
        apply device_aug_pure() itself (in-graph). Only meaningful in
        device-augment mode — returns whether the switch engaged.
        Always flip back off (try/finally) so other consumers of the
        same iterator see augmented batches again: the fused eval
        window (module/fused_eval.py) and the per-batch score/predict
        loops all draw through the eager per-batch augment path."""
        if not self._device_augment:
            return False
        self._defer_aug = bool(on)
        return True

    def iter_next(self):
        if self._pending is not None:
            return True
        if self._exhausted:
            return False
        try:
            self._pending = self.next()
            return True
        except StopIteration:
            return False


class ImageDetRecordIter(DataIter):
    """Detection RecordIO pipeline — reference src/io/iter_image_det_recordio.cc.

    Records are packed by tools/im2rec.py with ``--pack-label`` from a
    detection .lst: label = [header_width, object_width, (extra header...),
    then per-object rows of object_width values, conventionally
    [class_id, xmin, ymin, xmax, ymax, ...]].

    Labels are padded to a common (max_objects, object_width) block with
    ``label_pad_value`` (reference's DefaultPadLabel), so a batch is one
    dense (B, max_objects*object_width [+2 header]) array — dynamic object
    counts never reach the device, which is what XLA needs.
    """

    @staticmethod
    def _is_det_header(lab):
        """Packed-label detection header: [hdr_w>=2, obj_w>=1, ...] with the
        body an exact multiple of obj_w (iter_image_det_recordio.cc
        ImageDetLabelMap sanity checks)."""
        if lab.size < 2:
            return False
        hdr_w, ow = float(lab[0]), float(lab[1])
        if hdr_w < 2 or ow < 1 or hdr_w != int(hdr_w) or ow != int(ow):
            return False
        body = lab.size - int(hdr_w)
        return body >= 0 and body % int(ow) == 0

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=-1,
                 label_pad_width=-1, label_pad_value=-1.0, shuffle=False,
                 mean_r=0, mean_g=0, mean_b=0, std_r=1, std_g=1, std_b=1,
                 scale=1.0, rand_mirror=False, round_batch=True, **kwargs):
        super().__init__(batch_size)
        self.data_shape = tuple(data_shape)
        data, raw_labels = _read_imgrec(path_imgrec, self.data_shape, scale,
                                        (mean_r, mean_g, mean_b),
                                        (std_r, std_g, std_b))

        # normalize labels to [hdr_w, obj_w, objects...]
        parsed = []
        max_objs = 0
        obj_w = None
        for rec_i, lab in enumerate(raw_labels):
            lab = np.atleast_1d(np.asarray(lab, dtype=np.float32))
            if self._is_det_header(lab):
                ow = int(lab[1])
                body = lab[int(lab[0]):]
            else:  # plain label row: promote to 1 object row
                ow = max(int(lab.size), 1)
                body = lab
            if obj_w is None:
                obj_w = ow
            elif ow != obj_w:
                raise ValueError(
                    'record %d: inconsistent object width: %d vs %d'
                    % (rec_i, ow, obj_w))
            objs = body.reshape(-1, obj_w) if body.size else \
                np.zeros((0, obj_w), np.float32)
            parsed.append(objs)
            max_objs = max(max_objs, objs.shape[0])
        # the flat label pads to EXACTLY label_pad_width (or wider if the
        # data needs it) so train/val iterators built with the same pad
        # width always shape-match — the request need not be object-aligned
        width = 2 + max_objs * obj_w
        if label_pad_width > 0:
            width = max(width, label_pad_width)
        self.label_object_width = obj_w
        self.max_objects = max_objs

        label = np.full((len(parsed), width), label_pad_value,
                        dtype=np.float32)
        label[:, 0] = 2.0
        label[:, 1] = float(obj_w)
        for i, objs in enumerate(parsed):
            label[i, 2:2 + objs.size] = objs.ravel()

        self._inner = NDArrayIter(
            data, label, batch_size=batch_size, shuffle=shuffle,
            last_batch_handle='pad' if round_batch else 'discard')
        self._rand_mirror = rand_mirror
        self._pad_value = label_pad_value

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def _mirror_batch(self, batch):
        """Horizontal flip + x-coordinate label flip (reference
        DefaultImageDetAugmenter HorizontalFlip: normalized [0,1] coords,
        xmin' = 1-xmax, xmax' = 1-xmin for [id,xmin,ymin,xmax,ymax,...])."""
        data = [d.flip(axis=3) if d.ndim == 4 else d for d in batch.data]
        labels = []
        for lab_nd in batch.label:
            lab = lab_nd.asnumpy().copy()
            ow = self.label_object_width
            if ow >= 5:
                # only the object-aligned block holds boxes; any extra
                # label_pad_width tail cells are pure padding
                end = 2 + self.max_objects * ow
                objs = lab[:, 2:end].reshape(lab.shape[0], -1, ow)
                valid = objs[:, :, 0] != self._pad_value
                xmin = objs[:, :, 1].copy()
                xmax = objs[:, :, 3].copy()
                objs[:, :, 1] = np.where(valid, 1.0 - xmax, objs[:, :, 1])
                objs[:, :, 3] = np.where(valid, 1.0 - xmin, objs[:, :, 3])
                lab[:, 2:end] = objs.reshape(lab.shape[0], -1)
            labels.append(array(lab))
        return DataBatch(data, labels, batch.pad, batch.index,
                         provide_data=batch.provide_data,
                         provide_label=batch.provide_label)

    def next(self):
        batch = self._inner.next()
        if self._rand_mirror and _random.host_rng().rand() < 0.5:
            batch = self._mirror_batch(batch)
        return batch

    def iter_next(self):
        return self._inner.iter_next()


class MXDataIter(DataIter):
    """Wrapper around an engine-owned iterator handle (reference
    io.py:758 wraps a ctypes DataIterHandle; here the handle IS the
    underlying python iterator object — the same object the C ABI's
    MXDataIterCreateIter hands out through the embedded interpreter).
    Exposes the handle-style protocol: next/getdata/getlabel/getpad
    with single-buffer semantics."""

    def __init__(self, handle, data_name='data',
                 label_name='softmax_label', **_):
        if not isinstance(handle, DataIter):
            raise TypeError('MXDataIter wraps a data-iterator handle; '
                            'got %r' % (handle,))
        super().__init__(getattr(handle, 'batch_size', 1))
        self.handle = handle
        self._debug_skip_load = False
        self.first_batch = handle.next()
        data = self.first_batch.data[0]
        self.provide_data = [DataDesc(data_name, data.shape, data.dtype)]
        if self.first_batch.label:
            label = self.first_batch.label[0]
            self.provide_label = [DataDesc(label_name, label.shape,
                                           label.dtype)]
        else:
            self.provide_label = []
        self._current = None

    def debug_skip_load(self):
        """Reference parity: skip loading and return the first batch."""
        self._debug_skip_load = True

    def reset(self):
        self._current = None
        self.first_batch = None
        self.handle.reset()

    def next(self):
        if self._debug_skip_load and self.first_batch is not None:
            self._current = self.first_batch
            return self.first_batch
        if self.first_batch is not None:
            batch, self.first_batch = self.first_batch, None
            self._current = batch
            return batch
        self._current = self.handle.next()
        return self._current

    def iter_next(self):
        try:
            self.next()
            return True
        except StopIteration:
            self._current = None
            return False

    def getdata(self):
        return self._current.data[0]

    def getlabel(self):
        return self._current.label[0] if self._current.label else None

    def getindex(self):
        return getattr(self._current, 'index', None)

    def getpad(self):
        return getattr(self._current, 'pad', 0) or 0
