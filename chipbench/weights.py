"""Seeded random weights for a dense decoder, made by the benchmark.

One jitted call builds every leaf on the device in the dtype it is served
in (matrices bf16, norm scales f32), laid out as the serving program's
``Model`` expects its parameter tree. The plain reference reads the same
tree, so neither side takes weights from the program.

Scales are chosen so the model is far from degenerate: unit-variance
embeddings, fan-in matrices, non-zero attention biases and norm scales
near 1 make the logits depend on the context.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.modelcfg import Arch

def _key(seed: int) -> jax.Array:
    """A key from any non-negative seed (wider than 32 bits included)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(a: Arch) -> Dict:
    """Leaf shapes and dtypes, as a tree of (shape, dtype, std, mean)."""
    d, h, kv, hd, f, v, n = a.d_model, a.n_heads, a.n_kv_heads, a.head_dim, a.d_ff, a.vocab, a.n_layers
    bf, f32 = jnp.bfloat16, jnp.float32
    attn = {
        "wq": ((n, d, h, hd), bf, d ** -0.5, 0.0),
        "wk": ((n, d, kv, hd), bf, d ** -0.5, 0.0),
        "wv": ((n, d, kv, hd), bf, d ** -0.5, 0.0),
        "wo": ((n, h, hd, d), bf, (h * hd) ** -0.5, 0.0),
    }
    if a.qkv_bias:
        attn.update({
            "bq": ((n, h, hd), bf, 0.1, 0.0),
            "bk": ((n, kv, hd), bf, 0.1, 0.0),
            "bv": ((n, kv, hd), bf, 0.1, 0.0),
        })
    tree = {
        "embed": ((v, d), bf, 1.0, 0.0),
        "blocks": {
            "norm1": ((n, d), f32, 0.05, 1.0),
            "attn": attn,
            "norm2": ((n, d), f32, 0.05, 1.0),
            "ffn": {
                "w_gate": ((n, d, f), bf, d ** -0.5, 0.0),
                "w_up": ((n, d, f), bf, d ** -0.5, 0.0),
                "w_down": ((n, f, d), bf, f ** -0.5, 0.0),
            },
        },
        "final_norm": ((d,), f32, 0.05, 1.0),
    }
    if not a.tie_embeddings:
        tree["lm_head"] = ((d, v), bf, d ** -0.5, 0.0)
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 4 and isinstance(x[0], tuple)


def make(a: Arch, seed: int, device=None):
    """All weights, on ``device``, from ``seed``: one jitted call."""
    spec = shapes(a)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = [
            (mean + std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
            for k, (shape, dtype, std, mean) in zip(keys, leaves)
        ]
        return jax.tree.unflatten(treedef, out)

    sharding = None
    if device is not None:
        sharding = jax.sharding.SingleDeviceSharding(device)
    fn = jax.jit(build, out_shardings=sharding)
    return jax.block_until_ready(fn(_key(seed)))
