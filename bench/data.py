"""Seeded data for the benchmark: a copy of ``repro.data.synthetic``'s
``kpca_dataset`` and ``distribute``, kept here so that the inputs the
program receives cannot change with the program.

MNIST (the paper's data, arXiv:2211.15953 section 6.1) is not in the
repository; this is the repository's digits-like stand-in at M=784:
a dominant nonlinear factor, class offsets, weak secondary factors and
noise, squashed to [0, 1].
"""

from __future__ import annotations

import numpy as np


def sub_seed(seed: int, *stream: int) -> int:
    """A 32-bit seed for stream ``stream`` of run seed ``seed`` (any
    non-negative integer, also beyond 32 bits)."""
    return int(np.random.SeedSequence([int(seed), *stream]).generate_state(1)[0])


def kpca_dataset(n: int, m: int = 784, n_classes: int = 4, seed: int = 0,
                 noise: float = 0.05, dominant: float = 3.0) -> np.ndarray:
    """Nonlinear data with a *dominant* first kernel principal component
    (digits-like regime: MNIST's 0/3/5/8 kernel spectrum has a clear gap,
    which is what makes the paper's similarity metric well-conditioned).

    Structure: one strong shared nonlinear factor (amplitude ``dominant``)
    + per-class offsets + weak secondary factors + isotropic noise, embedded
    into R^m by a frozen random map and squashed to [0, 1].
    Returns (n, m) float32.
    """
    rng = np.random.default_rng(seed)
    latent_dim = 6
    # frozen embedding maps
    w_dom = rng.normal(0, 1.0, size=(2, m)) / np.sqrt(2)
    w_sec = rng.normal(0, 1.0, size=(latent_dim, m)) / np.sqrt(latent_dim)
    offs = rng.normal(0, 0.6, size=(n_classes, m))
    labels = np.arange(n) % n_classes
    # dominant shared 1-D nonlinear factor (a curve, not a line). The
    # harmonic amplitudes are ASYMMETRIC (4/3:1 vs dominant) so the global
    # kernel has a clear top-eigenvalue gap (~2.7-3.0 across seeds at
    # M=784) — symmetric amplitudes create a degenerate top pair that makes
    # the paper's top-1 similarity metric ill-posed for any solver.
    t = rng.uniform(0, 2 * np.pi, size=(n,))
    dom = np.stack([(4.0 / 3.0) * dominant * np.cos(t),
                    0.5 * dominant * np.sin(2 * t)], axis=1)        # (n, 2)
    # weak secondary factors
    sec = np.tanh(rng.normal(0, 1.0, size=(n, latent_dim))) * 0.4
    x = dom @ w_dom + sec @ w_sec + offs[labels]
    x = x + rng.normal(0, noise * np.sqrt(m) / 4, size=(n, m))
    x = 1.0 / (1.0 + np.exp(-x / np.sqrt(m) * 8.0))                 # [0, 1]
    perm = rng.permutation(n)
    return x[perm].astype(np.float32)


def distribute(x: np.ndarray, n_nodes: int, seed: int = 0) -> np.ndarray:
    """Randomly, evenly distribute samples to nodes: (J, N_j, M).
    Truncates the remainder (paper uses exactly even splits)."""
    rng = np.random.default_rng(seed)
    n = (x.shape[0] // n_nodes) * n_nodes
    perm = rng.permutation(x.shape[0])[:n]
    return x[perm].reshape(n_nodes, n // n_nodes, *x.shape[1:])
