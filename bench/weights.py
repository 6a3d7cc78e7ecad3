"""Seeded random weights for a configuration file, made on the device in
one jitted call, in the dtype they are served in.

The tree has the layout the serving program takes (a period-1 stack of
``<attn|swa>+moe`` blocks, leading layer axis), but it is written here
from the configuration file alone, so that the plain reference can make
the very same weights again without importing the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A JAX key from any whole number (seeds may pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.key(int(words[0]) & 0x7FFFFFFF)


def padded_vocab(model) -> int:
    """Embedding rows: the vocabulary rounded up to a multiple of 128."""
    return -(-model["vocab_size"] // 128) * 128


# Router logits of standard deviation ~4: trained routers are peaked, so
# the expert at the top-k boundary carries little gate weight and a
# rounding that swaps it for the next moves a token's output little.
ROUTER_STD = 4.0


def shapes(model):
    """{path: (shape, dtype, mean, std)} of every leaf, in a fixed order:
    each element is mean + std * N(0, 1).  The final norm's scale has
    mean 0: with a positive scale the tied head reads the current token's
    own embedding back from the residual stream, and greedy decoding of
    random weights then repeats one token for ever."""
    D, L, F = model["d_model"], model["n_layers"], model["d_ff"]
    H, Hkv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    E, V = model["num_experts"], padded_vocab(model)
    dt = model["dtype"]
    s_in, s_out = D ** -0.5, 1.0 / math.sqrt(2 * L)
    out = {
        ("embed", "table"): ((V, D), dt, 0.0, s_in),
        ("final_norm", "scale"): ((D,), dt, 0.0, 1.0),
        ("stack", "norm1", "scale"): ((L, D), dt, 1.0, 0.05),
        ("stack", "attn", "wq"): ((L, D, H, hd), dt, 0.0, s_in),
        ("stack", "attn", "wk"): ((L, D, Hkv, hd), dt, 0.0, s_in),
        ("stack", "attn", "wv"): ((L, D, Hkv, hd), dt, 0.0, s_in),
        ("stack", "attn", "wo"): ((L, H, hd, D), dt, 0.0, s_in * s_out),
        ("stack", "norm2", "scale"): ((L, D), dt, 1.0, 0.05),
        ("stack", "moe", "router"): ((L, D, E), "float32", 0.0,
                                     ROUTER_STD * s_in),
        ("stack", "moe", "experts", "w_gate"): ((L, E, D, F), dt, 0.0, s_in),
        ("stack", "moe", "experts", "w_up"): ((L, E, D, F), dt, 0.0, s_in),
        ("stack", "moe", "experts", "w_down"): ((L, E, F, D), dt, 0.0,
                                                F ** -0.5 * s_out),
    }
    if not model["tie_embeddings"]:
        out[("lm_head", "w")] = ((D, V), dt, 0.0, s_in)
    return out


def _nest(flat):
    tree = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def make_flat(model, seed: int):
    """{path: array} on the default device, one jitted call."""
    spec = shapes(model)

    def make(key):
        out = {}
        for i, (path, (shape, dt, mean, std)) in enumerate(spec.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[path] = (mean + std * z).astype(dt)
        return out

    return jax.jit(make)(seed_key(seed))


def program_params(model, seed: int):
    """The serving program's parameter tree: the stack is a one-element
    list (one pattern position) and there are no tail layers."""
    tree = _nest(make_flat(model, seed))
    tree["stack"] = [tree["stack"]]
    tree["tail"] = []
    return tree
