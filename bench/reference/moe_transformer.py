"""Plain float32 reference of a decoder of ``<attn|swa>+moe`` blocks with
HQQ-quantized weights, written from the configuration file alone.

It imports nothing of the serving program and takes nothing the program
made: it makes the seeded weights again (``bench/weights.py``) and
quantizes them itself, following the configuration's ``quant`` section.

The model, as the configuration states it:

* token embedding (the table's first ``vocab_size`` rows);
* per layer, pre-norm residual blocks: RMSNorm (``norm_eps``, learned
  scale); grouped-query attention with rotary embeddings applied to
  interleaved (even, odd) channel pairs, causal, optionally limited to a
  ``sliding_window``, softmax scale ``head_dim ** -0.5``; then RMSNorm
  and a top-``top_k`` mixture of SwiGLU experts: softmax over the router
  logits (float32), the ``top_k`` largest probabilities renormalised to
  sum to one;
* final RMSNorm and the unembedding (tied to the embedding when
  ``tie_embeddings``).

Quantized matrices are HQQ (Badri & Shaji 2023): group-wise affine codes
along the contraction axis, the zero point refined by ``iters`` rounds of
the half-quadratic l_p proximal step, and the per-group scale and zero
themselves quantized to 8 bits over ``scale_group`` groups, with their
step and minimum stored in float16.

Everything runs in float32 at ``default_matmul_precision("highest")``.
``act="fp8"`` computes the same model with every matmul operand that is
an activation rounded to 8-bit floats (e4m3, scaled per row so that its
largest magnitude is e4m3's largest) — the lower-precision control of
the check, one step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import weights as W  # noqa: E402  (bench/weights.py)

NEG = -1e30


# ----------------------------------------------------------------------
# HQQ, as the configuration states it
def _shrink(x, beta, p):
    return jnp.sign(x) * jax.nn.relu(
        jnp.abs(x) - (1.0 / beta) * jnp.power(jnp.abs(x) + 1e-8, p - 1.0))


def _meta(a, scale_group):
    """8-bit codes of a (..., G, 1, N) array over groups of ``scale_group``
    along G, returned dequantized (float16 step and minimum)."""
    *lead, G, one, N = a.shape
    sg = min(scale_group, G)
    while G % sg:
        sg //= 2
    ar = a.reshape(*lead, G // sg, sg, one, N)
    mn = ar.min(axis=-3, keepdims=True)
    mx = ar.max(axis=-3, keepdims=True)
    s = jnp.where(mx - mn <= 1e-12, 1.0, (mx - mn) / 255.0)
    q = jnp.clip(jnp.round((ar - mn) / s), 0, 255)
    s16 = s.astype(jnp.float16).astype(jnp.float32)
    mn16 = mn.astype(jnp.float16).astype(jnp.float32)
    return (q * s16 + mn16).reshape(a.shape)


def hqq_roundtrip(w, *, bits, group_size, scale_group, hqq):
    """Quantize ``w`` (..., K, N) along K and return it dequantized, f32."""
    *lead, K, N = w.shape
    g = group_size
    wg = w.reshape(*lead, K // g, g, N).astype(jnp.float32)
    maxv = 2.0 ** bits - 1.0
    wmin = wg.min(axis=-2, keepdims=True)
    wmax = wg.max(axis=-2, keepdims=True)
    scale = (wmax - wmin) / maxv
    scale = jnp.where(scale <= 1e-8, 1.0, scale)
    zero = -wmin / scale
    beta = jnp.float32(hqq["beta0"])
    for _ in range(hqq["iters"]):
        q = jnp.clip(jnp.round(wg / scale + zero), 0, maxv)
        we = _shrink(wg - (q - zero) * scale, beta, hqq["lp"])
        zero = jnp.mean(q - (wg - we) / scale, axis=-2, keepdims=True)
        beta = beta * hqq["kappa"]
    q = jnp.clip(jnp.round(wg / scale + zero), 0, maxv)
    scale, zero = _meta(scale, scale_group), _meta(zero, scale_group)
    return ((q - zero) * scale).reshape(w.shape)


def dequantized(w, spec, hqq):
    """``w`` as the configuration stores it: HQQ round trip, or the
    16-bit values themselves (``bits`` 16), in float32."""
    if spec["bits"] >= 16:
        return w.astype(jnp.float32)
    return hqq_roundtrip(w, bits=spec["bits"], group_size=spec["group_size"],
                         scale_group=spec["scale_group"], hqq=hqq)


# ----------------------------------------------------------------------
E4M3_MAX = 448.0


def _fq(x, act):
    """``x`` rounded to the activation precision ``act`` ("" keeps it)."""
    if not act:
        return x
    if act != "fp8":
        raise ValueError(f"unknown activation precision {act!r}")
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / E4M3_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (S, heads, hd): rotate interleaved (even, odd) channel pairs."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, hd/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


@partial(jax.jit, static_argnames=("m", "act"))
def _attn_block(x, n1, wq, wk, wv, wo, *, m, act):
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    S = x.shape[0]
    h = _fq(_rms(x, n1, m["norm_eps"]), act)
    f32 = lambda a: a.astype(jnp.float32)
    q = jnp.einsum("sd,dhk->shk", h, f32(wq))
    k = jnp.einsum("sd,dhk->shk", h, f32(wk))
    v = jnp.einsum("sd,dhk->shk", h, f32(wv))
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    q, k, v = _fq(q, act), _fq(k, act), _fq(v, act)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    ok = ki <= qi
    if m.get("sliding_window"):
        ok &= (qi - ki) < m["sliding_window"]
    p = jax.nn.softmax(jnp.where(ok[None], s, NEG), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
    return x + _fq(o, act) @ wo


@partial(jax.jit, static_argnames=("m",))
def _route(x, n2, router, *, m):
    """(RMSNorm output, (S, E) gate weights: the renormalised top-k)."""
    h = _rms(x, n2, m["norm_eps"])
    probs = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    top, ids = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.clip(top.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ids].set(top)
    return h, gates


@partial(jax.jit, static_argnames=("act",))
def _expert(y, h, gate, w_gate, w_up, w_down, *, act):
    """y + gate * SwiGLU expert(h); gate (S, 1)."""
    hq = _fq(h, act)
    a = _fq(jax.nn.silu(hq @ w_gate) * (hq @ w_up), act)
    return y + gate * (a @ w_down)


@partial(jax.jit, static_argnames=("m", "act"))
def _head(x, fnorm, table, *, m, act):
    h = _fq(_rms(x, fnorm, m["norm_eps"]), act)
    return h @ table.astype(jnp.float32)


class Reference:
    """The configuration's model with the weights of ``seed``.  Quantized
    expert matrices are dequantized one expert at a time and applied to
    every sequence before the next, so only one expert's float32 copy is
    on the device at a time."""

    def __init__(self, config, seed: int):
        self.m = _frozen(config["model"])
        self.q = config["quant"]
        self.w = W.make_flat(self.m, seed)
        # wo is quantized as one (layers * heads * head_dim, d_model) stack
        wo = self.w[("stack", "attn", "wo")]
        L, H, hd, D = wo.shape
        with jax.default_matmul_precision("highest"):
            self.wo = jax.jit(partial(dequantized, spec=self.q["wo"],
                                      hqq=self.q["hqq"]))(
                wo.reshape(L * H * hd, D)).reshape(L, H * hd, D)
        V = self.m["vocab_size"]
        if self.m["tie_embeddings"]:
            self.table = self.w[("embed", "table")][:V].T
        else:
            self.table = self.w[("lm_head", "w")][:, :V]
        self._dq = jax.jit(partial(dequantized, spec=self.q["experts"],
                                   hqq=self.q["hqq"]))

    def logits(self, seqs, acts=("",)):
        """Teacher-forced logits of every position of each token sequence:
        returns ``{act: [(S_i, vocab_size) float32 ...]}`` for each entry
        of ``acts`` ("" = float32 activations)."""
        m, w = self.m, self.w
        runs = [(b, i) for b in acts for i in range(len(seqs))]
        with jax.default_matmul_precision("highest"):
            emb = w[("embed", "table")]
            xs = [emb[jnp.asarray(seqs[i])].astype(jnp.float32)
                  for _, i in runs]
            for l in range(m["n_layers"]):
                xs = [_attn_block(
                    x, w[("stack", "norm1", "scale")][l],
                    w[("stack", "attn", "wq")][l],
                    w[("stack", "attn", "wk")][l],
                    w[("stack", "attn", "wv")][l], self.wo[l],
                    m=m, act=b) for x, (b, _) in zip(xs, runs)]
                routed = [_route(x, w[("stack", "norm2", "scale")][l],
                                 w[("stack", "moe", "router")][l], m=m)
                          for x in xs]
                ys = list(xs)
                for e in range(m["num_experts"]):
                    ex = [self._dq(w[("stack", "moe", "experts", n)][l, e])
                          for n in ("w_gate", "w_up", "w_down")]
                    ys = [_expert(y, h, g[:, e:e + 1], *ex, act=b)
                          for y, (h, g), (b, _) in zip(ys, routed, runs)]
                    del ex
                xs = ys
            out = [np.asarray(_head(x, w[("final_norm", "scale")],
                                    self.table, m=m, act=b))
                   for x, (b, _) in zip(xs, runs)]
        res = {b: [None] * len(seqs) for b in acts}
        for o, (b, i) in zip(out, runs):
            res[b][i] = o
        return res


class _frozen(dict):
    """A hashable view of the model dict (a static jit argument)."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))

