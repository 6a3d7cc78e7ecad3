"""The plain float32 reference of ``moe_transformer.py`` — the same model,
the same seeded weights and the same mathematics — for a chip that still
holds the served program while the check runs: it never holds the whole
expert stacks at once.

Each expert stack (``w_gate``, ``w_up``, ``w_down``) is made whole only
for a moment, as ``bench/weights.make_flat`` makes it (the same random
normal of the same shape and key), when the forward pass first reaches a
layer, and only that layer's slice of it is kept until the pass moves on
to the next layer.  Every other weight is made whole, as there.

Why: at Mixtral-8x7B's widths four layers of expert stacks are 11.27 GB
of bfloat16.  ``bench/run.py`` keeps the served program (its packed
store, expert pool and other weights, ~4.6 GB) alive through the check,
so the whole stacks do not fit beside it; one stack and a layer of the
others (~6.6 GB) do.
"""
from __future__ import annotations

import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent))
import moe_transformer as MT  # noqa: E402

W = MT.W


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _leaf(key, i, shape, dt, mean, std):
    """Leaf ``i`` of ``bench/weights.make_flat``, made as it makes it."""
    z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return (mean + std * z).astype(dt)


class _ByLayer:
    """An expert stack read as ``stack[l, e]``: it keeps layer ``l`` of
    the stack and makes the stack again, to keep the next layer, when
    another layer is asked for."""

    def __init__(self, key, i, spec):
        self.key, self.i, self.spec = key, i, spec
        self.layer, self.kept = None, None

    def __getitem__(self, idx):
        l, e = idx
        if l != self.layer:
            self.kept = None  # freed before the stack is made again
            self.kept = _leaf(self.key, self.i, *self.spec)[l]
            self.layer = l
        return self.kept[e]


class Reference(MT.Reference):
    """``moe_transformer.Reference`` with each expert stack held a layer
    at a time (:class:`_ByLayer`)."""

    def __init__(self, config, seed: int):
        self.m = MT._frozen(config["model"])
        self.q = config["quant"]
        key = W.seed_key(seed)
        self.w = {path: (_ByLayer(key, i, spec) if "experts" in path
                         else _leaf(key, i, *spec))
                  for i, (path, spec) in enumerate(W.shapes(self.m).items())}
        # as moe_transformer.Reference: wo is quantized as one stack
        wo = self.w[("stack", "attn", "wo")]
        L, H, hd, D = wo.shape
        with jax.default_matmul_precision("highest"):
            self.wo = jax.jit(partial(MT.dequantized, spec=self.q["wo"],
                                      hqq=self.q["hqq"]))(
                wo.reshape(L * H * hd, D)).reshape(L, H * hd, D)
        V = self.m["vocab_size"]
        if self.m["tie_embeddings"]:
            self.table = self.w[("embed", "table")][:V].T
        else:
            self.table = self.w[("lm_head", "w")][:, :V]
        self._dq = jax.jit(partial(MT.dequantized, spec=self.q["experts"],
                                   hqq=self.q["hqq"]))
