"""Datasets for the FL experiments and the LM examples.

MNIST is not available offline in this container (DESIGN.md §3), so
``make_mnist_like`` procedurally generates a deterministic 10-class 28x28
dataset with MNIST-like difficulty: each class has a smoothed stroke
prototype; samples add jitter (shift) and pixel noise. A loader hook
(`load_mnist_npz`) reads a real ``mnist.npz`` when its path is given
explicitly, keeping the pipeline identical; without a path the data comes
from the seed.

``token_stream`` provides synthetic LM token batches for the transformer
examples (power-law unigram with Markov structure so the loss has signal).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def _class_prototypes(n_classes: int, side: int, rng) -> np.ndarray:
    """Smoothed random stroke patterns, one per class — stable, separable."""
    protos = np.zeros((n_classes, side, side), np.float32)
    for c in range(n_classes):
        img = np.zeros((side, side), np.float32)
        # draw 3 random strokes (line segments) per class
        for _ in range(3):
            x0, y0 = rng.integers(4, side - 4, 2)
            ang = rng.uniform(0, 2 * np.pi)
            length = rng.integers(8, side - 6)
            for t in np.linspace(0, 1, 60):
                x = int(np.clip(x0 + np.cos(ang) * t * length, 0, side - 1))
                y = int(np.clip(y0 + np.sin(ang) * t * length, 0, side - 1))
                img[y, x] = 1.0
        # box-blur twice for stroke thickness
        for _ in range(2):
            img = (img
                   + np.roll(img, 1, 0) + np.roll(img, -1, 0)
                   + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
        protos[c] = img / max(img.max(), 1e-6)
    return protos


def make_mnist_like(n_train: int = 20000, n_test: int = 4000,
                    n_classes: int = 10, side: int = 28, noise: float = 0.25,
                    seed: int = 1234) -> Tuple[np.ndarray, np.ndarray,
                                               np.ndarray, np.ndarray]:
    """Returns (x_train (N,784) float32 in [0,1], y_train, x_test, y_test)."""
    rng = np.random.default_rng(seed)
    protos = _class_prototypes(n_classes, side, rng)

    def gen(n):
        y = rng.integers(0, n_classes, n).astype(np.int32)
        x = np.empty((n, side * side), np.float32)
        shifts = rng.integers(-2, 3, size=(n, 2))
        for i in range(n):
            img = protos[y[i]]
            img = np.roll(img, shifts[i, 0], axis=0)
            img = np.roll(img, shifts[i, 1], axis=1)
            img = img + noise * rng.standard_normal((side, side)).astype(np.float32)
            x[i] = np.clip(img, 0.0, 1.0).reshape(-1)
        return x, y

    x_tr, y_tr = gen(n_train)
    x_te, y_te = gen(n_test)
    return x_tr, y_tr, x_te, y_te


def load_mnist_npz(path: str):
    """Real MNIST from a .npz with x_train/y_train/x_test/y_test (same
    interface as make_mnist_like)."""
    with np.load(path) as z:
        x_tr = z["x_train"].reshape(len(z["x_train"]), -1).astype(np.float32) / 255.0
        x_te = z["x_test"].reshape(len(z["x_test"]), -1).astype(np.float32) / 255.0
        return (x_tr, z["y_train"].astype(np.int32), x_te,
                z["y_test"].astype(np.int32))


def get_dataset(path: Optional[str] = None, **kw):
    """Real MNIST from ``path`` when one is given; otherwise the seeded
    ``make_mnist_like`` data (``kw`` are its arguments)."""
    if path is not None:
        return load_mnist_npz(path)
    return make_mnist_like(**kw)


# ---------------------------------------------------------------------------
# synthetic LM tokens (transformer examples / integration tests)
# ---------------------------------------------------------------------------

def token_stream(vocab: int, batch: int, seq: int, n_batches: int,
                 seed: int = 0):
    """Markov-ish synthetic token batches: next token = (prev*a + c) % vocab
    with noise — learnable structure, zero storage."""
    rng = np.random.default_rng(seed)
    a = 31 % vocab or 1
    for _ in range(n_batches):
        x = np.empty((batch, seq), np.int64)
        x[:, 0] = rng.integers(0, vocab, batch)
        flip = rng.random((batch, seq)) < 0.1
        for t in range(1, seq):
            nxt = (x[:, t - 1] * a + 7) % vocab
            x[:, t] = np.where(flip[:, t], rng.integers(0, vocab, batch), nxt)
        yield {"tokens": x.astype(np.int32)}
