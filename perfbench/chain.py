"""The weight-version chain of the RL weight-sync traffic.

From the seed, on the device, in one jitted call: float32 master weights
at the model's shapes, then AdamW steps on seeded Gaussian gradients, each
version published as the model's dtype.  At an RL learning rate most
weights move by less than their last bit per step, so consecutive versions
differ in a few percent of their elements: the sparse deltas that weight
sync lives on.  The window publishes the versions ping-pong
(0, 1, 2, 1, 0, 1, ...), so every round is a one-step delta however many
rounds fit into the window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number: 64 bits, both halves kept."""
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _is_norm(path) -> bool:
    return any("norm" in str(getattr(k, "key", k)) for k in path)


def make_chain(shapes, key, *, versions: int, lr: float, beta1: float,
               beta2: float, eps: float, weight_decay: float,
               init_std: float, device=None):
    """``versions`` weight trees shaped like ``shapes`` (a pytree of shape
    structs), v0 the seeded init and v(i+1) one AdamW step after v(i)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = [[] for _ in range(versions)]
        for i, (path, s) in enumerate(flat):
            k = jax.random.fold_in(key, i)
            if _is_norm(path):
                w = jnp.ones(s.shape, jnp.float32)
            else:
                w = init_std * jax.random.normal(k, s.shape, jnp.float32)
            m = jnp.zeros_like(w)
            v = jnp.zeros_like(w)
            out[0].append(w.astype(s.dtype))
            for t in range(1, versions):
                g = jax.random.normal(jax.random.fold_in(k, t), s.shape,
                                      jnp.float32)
                m = beta1 * m + (1 - beta1) * g
                v = beta2 * v + (1 - beta2) * g * g
                m_hat = m / (1 - beta1 ** t)
                v_hat = v / (1 - beta2 ** t)
                w = w - lr * (m_hat / (jnp.sqrt(v_hat) + eps)
                              + weight_decay * w)
                out[t].append(w.astype(s.dtype))
        return tuple(jax.tree_util.tree_unflatten(treedef, o) for o in out)

    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(build)(key)


def ping_pong(n_versions: int, start: int = 0):
    """Indices 0, 1, ..., n-1, n-2, ..., 0, 1, ... from ``start`` on."""
    period = 2 * (n_versions - 1)
    i = start
    while True:
        j = i % period
        yield j if j < n_versions else period - j
        i += 1
