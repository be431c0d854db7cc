"""Client datasets of a cell, drawn from the run seed.

The traffic file's ``data`` entry names a generator by ``kind``:

``digits``: the MNIST-like 10-class 28x28 images of the paper federation
(arXiv:2305.04066 Sec. IV-A) over a non-IID partition: each client's size
comes from the ``sizes`` ladder (drawn from the cell's fixed layout seed),
and it draws at most ``max_classes`` of the ten classes, split evenly. An image is its class's stroke prototype, shifted
by up to two pixels each way, plus Gaussian pixel noise, clipped to
[0, 1]. This follows ``make_mnist_like`` and ``partition_noniid`` of the
program's data package, vectorised, with each client's samples drawn
fresh rather than from a shared pool.

``tokens``: Markov-like token rows for language-model clients, as the
program's ``token_stream``: the next token is ``(prev * 31 + 7) % vocab``
except where a ``flip`` share of positions draws a uniform token.

Both return a ``Federation`` of host arrays: every client's rows, and
the zero-padded stack the reference trains from.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

SIDE = 28
N_CLASSES = 10


@dataclass
class Federation:
    x: np.ndarray          # (K, n_max, ...) zero-padded features or tokens
    y: np.ndarray          # (K, n_max) int32 labels (zeros for tokens)
    n: np.ndarray          # (K,) int32 rows held by each client

    def client(self, k: int):
        return self.x[k, :self.n[k]], self.y[k, :self.n[k]]


def _class_prototypes(rng) -> np.ndarray:
    """Smoothed random stroke patterns, one per class."""
    protos = np.zeros((N_CLASSES, SIDE, SIDE), np.float32)
    for c in range(N_CLASSES):
        img = np.zeros((SIDE, SIDE), np.float32)
        for _ in range(3):
            x0, y0 = rng.integers(4, SIDE - 4, 2)
            ang = rng.uniform(0, 2 * np.pi)
            length = rng.integers(8, SIDE - 6)
            t = np.linspace(0, 1, 60)
            xs = np.clip(x0 + np.cos(ang) * t * length, 0, SIDE - 1)
            ys = np.clip(y0 + np.sin(ang) * t * length, 0, SIDE - 1)
            img[ys.astype(int), xs.astype(int)] = 1.0
        for _ in range(2):
            img = (img + np.roll(img, 1, 0) + np.roll(img, -1, 0)
                   + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
        protos[c] = img / max(img.max(), 1e-6)
    return protos


def digits(seed: int, layout_seed: int, k: int, spec: dict) -> Federation:
    sizes = np.asarray(spec["sizes"])
    n = np.random.default_rng(layout_seed).choice(sizes, size=k).astype(
        np.int32)
    rng = np.random.default_rng(seed)
    protos = _class_prototypes(rng)
    labels = np.zeros((k, int(sizes.max())), np.int32)
    for i in range(k):
        n_cls = int(rng.integers(1, spec["max_classes"] + 1))
        cls = rng.choice(N_CLASSES, size=n_cls, replace=False)
        chunks = np.array_split(np.arange(n[i]), n_cls)
        labels[i, :n[i]] = np.concatenate(
            [np.full(len(ch), c, np.int32) for c, ch in zip(cls, chunks)])
    real = np.arange(labels.shape[1])[None, :] < n[:, None]
    y = labels[real]
    shifts = rng.integers(-2, 3, size=(len(y), 2))
    rows = (np.arange(SIDE)[None, :] - shifts[:, :1]) % SIDE
    cols = (np.arange(SIDE)[None, :] - shifts[:, 1:]) % SIDE
    img = protos[y[:, None, None], rows[:, :, None], cols[:, None, :]]
    img += np.float32(spec["pixel_noise"]) * rng.standard_normal(
        img.shape, dtype=np.float32)
    x = np.zeros((k, labels.shape[1], SIDE * SIDE), np.float32)
    x[real] = np.clip(img, 0.0, 1.0).reshape(len(y), -1)
    return Federation(x=x, y=labels, n=n)


def tokens(seed: int, k: int, spec: dict, vocab: int) -> Federation:
    rng = np.random.default_rng(seed)
    s, t = spec["sequences"], spec["seq_len"]
    x = np.empty((k, s, t), np.int64)
    x[..., 0] = rng.integers(0, vocab, (k, s))
    flip = rng.random((k, s, t)) < spec["flip"]
    draws = rng.integers(0, vocab, (k, s, t))
    for j in range(1, t):
        x[..., j] = np.where(flip[..., j], draws[..., j],
                             (x[..., j - 1] * 31 + 7) % vocab)
    return Federation(x=x.astype(np.int32), y=np.zeros((k, s), np.int32),
                      n=np.full(k, s, np.int32))


def make(seed: int, layout_seed: int, k: int, spec: dict,
         cfg: dict) -> Federation:
    """``seed`` draws the rows; ``layout_seed`` the clients' sizes, which
    the program compiles in as constants."""
    if spec["kind"] == "digits":
        return digits(seed, layout_seed, k, spec)
    if spec["kind"] == "tokens":
        return tokens(seed, k, spec, cfg["vocab_size"])
    raise ValueError(f"unknown data kind {spec['kind']!r}")
