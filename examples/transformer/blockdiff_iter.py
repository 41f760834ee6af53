"""``BlockDiffusionIter``: the data side of block-diffusion training
(arXiv:2503.09573) for ``Module.fit``.

It wraps any ``DataIter`` whose first data array is ``(batch, L)`` clean
token ids and turns each batch into a step of
``examples/transformer/symbols/sdar_moe.py``:

    data          (batch, 2 L)   [xt ; x0]
    softmax_label (batch, L)     x0 where masked, -1 elsewhere
    loss_weight   (batch, L)     1 / t of the position's block

For every block of ``block_length`` positions of every row a noise level
``t`` is drawn uniformly from ``noise_t`` (0.45 to 0.95: the clipped
schedule arXiv:2503.09573 found best for blocks of 4) and each position of
the block is masked independently with probability ``t``: ``xt`` holds
``mask_id`` there and ``x0`` elsewhere. The generator is numpy's Philox
keyed by ``(seed, step)``, so the noise of step k is the same whoever asks
for it and in whatever order (:func:`noise` is the one function; a
reference calls it with the same arguments). All of it is the host's work,
about 100 KB a step at L = 4096, prepared a window ahead like any batch.
"""
import numpy as np

import mxnet_tpu as mx

IGNORE = -1.0


def noise(seed, step, batch, length, block_length, noise_t=(0.45, 0.95)):
    """(mask (batch, length) bool, weight (batch, length) float32 = 1 / t
    of each position's block) of step number `step`."""
    rng = np.random.Generator(np.random.Philox(key=[int(seed), int(step)]))
    blocks = length // block_length
    t = rng.uniform(noise_t[0], noise_t[1], size=(batch, blocks))
    t = np.repeat(t.astype(np.float32), block_length, axis=1)
    mask = rng.random(size=(batch, length), dtype=np.float32) < t
    return mask, (np.float32(1.0) / t)


def noised(x0, mask, weight, mask_id):
    """(data, softmax_label, loss_weight), float32, of clean ids `x0`."""
    x0 = np.asarray(x0, np.float32)
    xt = np.where(mask, np.float32(mask_id), x0)
    return (np.concatenate([xt, x0], axis=1),
            np.where(mask, x0, np.float32(IGNORE)),
            np.asarray(weight, np.float32))


class BlockDiffusionIter(mx.io.DataIter):
    """`clean`'s batches, noised. ``step`` counts the batches drawn since
    construction and keys the noise; ``reset`` resets `clean` and not the
    count, so that no two steps of a run share their noise."""

    def __init__(self, clean, block_length, mask_id, seed=0,
                 noise_t=(0.45, 0.95)):
        desc = clean.provide_data[0]
        batch, length = desc.shape
        if length % block_length:
            raise ValueError('BlockDiffusionIter: %d positions are not '
                             'whole blocks of %d' % (length, block_length))
        super().__init__(batch)
        self.clean, self.block_length = clean, int(block_length)
        self.mask_id, self.seed, self.noise_t = mask_id, seed, noise_t
        self.step = 0
        self.provide_data = [mx.io.DataDesc('data', (batch, 2 * length),
                                            np.float32)]
        self.provide_label = [
            mx.io.DataDesc('softmax_label', (batch, length), np.float32),
            mx.io.DataDesc('loss_weight', (batch, length), np.float32)]

    def reset(self):
        self.clean.reset()

    def next(self):
        batch = self.clean.next()
        x0 = batch.data[0].asnumpy()
        if (x0 == self.mask_id).any():
            raise ValueError('BlockDiffusionIter: the clean ids hold the '
                             'mask id %d' % self.mask_id)
        mask, weight = noise(self.seed, self.step, x0.shape[0], x0.shape[1],
                             self.block_length, self.noise_t)
        self.step += 1
        data, label, weight = noised(x0, mask, weight, self.mask_id)
        return mx.io.DataBatch(
            data=[mx.nd.array(data)],
            label=[mx.nd.array(label), mx.nd.array(weight)],
            pad=batch.pad, index=batch.index,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
