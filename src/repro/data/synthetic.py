"""Synthetic datasets (the container is offline — DESIGN.md §Assumptions).

- ``lm_batch``: token streams from a fixed-seed Zipf-ish categorical over
  the vocab with a deterministic next-token structure (so models can
  actually reduce loss — labels are a fixed permutation of the inputs
  mixed with noise).
- ``mnist_analog``: 10-class Gaussian-mixture in 784-d with class-dependent
  means — stands in for MNIST in the paper-replication experiments. Linear
  separability ~90%+ mirrors logistic-regression-on-MNIST behaviour.
- ``linreg`` (Proposition 1): y = x·w* + ξ with Rademacher or Gaussian x.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("batch", "seq", "vocab"))
def lm_batch(key, batch: int, seq: int, vocab: int) -> Dict[str, jax.Array]:
    """Learnable synthetic LM data: next token = (5·tok + 7) % vocab with
    probability 0.9, uniform noise otherwise.

    One compiled ``lax.scan`` over the sequence: dispatched eagerly, the
    per-position loop cost ~10 host dispatches per token, which at seq
    2048 took longer than the train step it fed.
    """
    k1, k2, k3 = jax.random.split(key, 3)
    first = jax.random.randint(k1, (batch, 1), 0, vocab)

    def step(tok, ks):
        knoise, kpick = ks
        nxt = (5 * tok + 7) % vocab
        noise = jax.random.randint(knoise, tok.shape, 0, vocab)
        pick = jax.random.bernoulli(kpick, 0.9, tok.shape)
        tok = jnp.where(pick, nxt, noise)
        return tok, tok

    keys = jax.random.split(k2, 2 * seq).reshape(seq, 2, -1)
    _, toks = jax.lax.scan(step, first[:, 0], keys)
    stream = jnp.concatenate([first, toks.T], axis=1)  # (B, seq+1)
    return {"tokens": stream[:, :-1], "labels": stream[:, 1:]}


def mnist_analog(key, n: int, d: int = 784, num_classes: int = 10,
                 noise: float = 1.0, mu_seed: int = 424242) -> Dict[str, jax.Array]:
    """10-class Gaussian mixture standing in for MNIST.

    The class means are drawn from the FIXED ``mu_seed`` so every worker
    shard and the test set sample the same population distribution (the
    paper's iid setting); ``key`` only drives the sample draw. Noise 1.0
    vs class-mean scale 3/√d gives linear test accuracy ~85% clean and a
    ~5-point drop under 5%-worker label flips through mean aggregation —
    mirroring logistic-regression-on-MNIST behaviour (tuned empirically).
    """
    mus = _class_means(num_classes, d, mu_seed)
    kx, ky = jax.random.split(key)
    y = jax.random.randint(ky, (n,), 0, num_classes)
    x = mus[y] + noise * jax.random.normal(kx, (n, d))
    return {"x": x, "y": y}


def _class_means(num_classes: int, d: int, mu_seed: int) -> jax.Array:
    """Class means with SPATIAL structure when d is a square image size:
    smooth low-res blobs upsampled (7x7 -> 28x28 for d=784), so that the
    paper's CNN experiment has conv/pool-compatible signal (white-noise
    means are destroyed by weight-shared convolution + pooling; a linear
    model doesn't care either way). Normalised to ||mu_c|| = 3."""
    key = jax.random.PRNGKey(mu_seed)
    side = int(round(d ** 0.5))
    if side * side == d and side % 4 == 0:
        low = jax.random.normal(key, (num_classes, side // 4, side // 4))
        mus = jnp.repeat(jnp.repeat(low, 4, axis=1), 4, axis=2).reshape(num_classes, d)
    else:
        mus = jax.random.normal(key, (num_classes, d))
    return 3.0 * mus / jnp.linalg.norm(mus, axis=1, keepdims=True)


def linreg(key, n: int, d: int, sigma: float, features: str = "rademacher"
           ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    kx, kn, kw = jax.random.split(key, 3)
    if features == "rademacher":
        x = jax.random.rademacher(kx, (n, d), dtype=jnp.float32)
    else:
        x = jax.random.normal(kx, (n, d))
    w_star = jax.random.normal(kw, (d,)) / jnp.sqrt(d)
    y = x @ w_star + sigma * jax.random.normal(kn, (n,))
    return {"x": x, "y": y}, w_star
