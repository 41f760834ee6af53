"""Seeded synthetic images (a copy of ``chip_smoke.py::synthetic_iter``'s
learnable data): ten of the classes, each a fixed random pattern, under
unit noise that differs in every row, so that a loss has somewhere to go
within a hundred steps and no two rows of a batch are alike."""
import numpy as np


def image_pool(seed, rows, image_shape, classes):
    """(images float32 (rows, C, H, W), labels int (rows,))."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    used = min(10, classes)
    patterns = rng.standard_normal((used,) + tuple(image_shape),
                                   dtype=np.float32)
    labels = rng.integers(0, used, rows)
    images = rng.standard_normal((rows,) + tuple(image_shape),
                                 dtype=np.float32)
    images += patterns[labels]
    return images, labels


def batch_offset(k, rows, batch):
    """Where batch number k starts in a pool of `rows`: a rolling offset,
    so that consecutive batches are different arrays."""
    span = rows - batch + 1
    return (k * 37) % span
