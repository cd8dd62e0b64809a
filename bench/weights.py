"""Random weights of a dense decoder, made on the device from the seed.

One jitted program makes every leaf, in the type the model is served
in, already placed where the server keeps it (`shardings`, for a
model divided over chips). The same seed gives the same weights. The
layout is the plain one that both the served program and the
reference read:

    embed [V, D]; final_norm [D]; unembed [D, V] (untied only);
    layers: attn_norm [L, D], wq [L, D, H, HD], wk/wv [L, D, KH, HD],
            wo [L, H, HD, D], mlp_norm [L, D], w_gate/w_up [L, D, F],
            w_down [L, F, D]

Matrices are normal with standard deviation 1/sqrt(fan-in), the
embedding 0.02, and norm weights uniform on [0.5, 1.5] so that a norm
applied without its weight shows in the outputs.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
          "float32": jnp.float32}


def shapes(sizes: Dict) -> Dict:
    """{leaf path: (shape, init, fan_in)} in a fixed order."""
    L, D, V = sizes["num_layers"], sizes["d_model"], sizes["vocab"]
    H, KH, HD = sizes["num_heads"], sizes["kv_heads"], sizes["head_dim"]
    F = sizes["d_ff"]
    out = {
        "embed": ((V, D), "embed", 0),
        "final_norm": ((D,), "norm", 0),
        "layers/attn_norm": ((L, D), "norm", 0),
        "layers/wq": ((L, D, H, HD), "normal", D),
        "layers/wk": ((L, D, KH, HD), "normal", D),
        "layers/wv": ((L, D, KH, HD), "normal", D),
        "layers/wo": ((L, H, HD, D), "normal", H * HD),
        "layers/mlp_norm": ((L, D), "norm", 0),
        "layers/w_gate": ((L, D, F), "normal", D),
        "layers/w_up": ((L, D, F), "normal", D),
        "layers/w_down": ((L, F, D), "normal", F),
    }
    if not sizes.get("tie_embeddings", False):
        out["unembed"] = ((D, V), "normal", D)
    return out


def _nest(flat: Dict) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return tree


def key_seed(seed: int, stream: int) -> int:
    """A 31-bit JAX seed from any whole-number `seed` (the benchmark's
    seeds exceed 32 bits), one per `stream`."""
    return int(np.random.default_rng([seed, stream]).integers(0, 2**31 - 1))


def make(sizes: Dict, dtype: str, seed: int, shardings=None) -> Dict:
    """The weights of `sizes` for `seed`, in one program on the device.
    `shardings` is a tree like the result (or None: one device)."""
    spec = shapes(sizes)
    dt = DTYPES[dtype]

    def init(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (path, (shape, kind, fan_in)) in zip(keys, spec.items()):
            if kind == "norm":
                x = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)
            else:
                std = 0.02 if kind == "embed" else 1.0 / math.sqrt(fan_in)
                x = jax.random.normal(k, shape, jnp.float32) * std
            flat[path] = x.astype(dt)
        return _nest(flat)

    fn = jax.jit(init, out_shardings=shardings)
    return jax.block_until_ready(fn(jax.random.key(key_seed(seed, 1))))

