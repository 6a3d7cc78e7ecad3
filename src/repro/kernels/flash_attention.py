"""Pallas TPU kernel: blockwise (flash) causal attention with GQA and
optional sliding window.

Grid: (batch*q_heads, Sq/bq, Skv/bk) with the KV dimension innermost; the
online-softmax running max / normalizer / accumulator live in VMEM scratch
and the normalized output is written on the last KV step.  GQA is handled
by the KV index map (``bh // group`` selects the shared KV head) — no KV
replication in memory.

With a causal sliding window the KV grid dimension shrinks to the blocks
that can intersect ``(qpos − window, qpos]`` for the step's query block:
the KV index map offsets each step by the block's window floor
(``lo(qi) + j``, clamped), so mask-only blocks are **dropped from the
grid** instead of visited-and-masked.  This is bitwise-neutral: a fully
masked *leading* block leaves ``m = −inf`` junk that the first valid
block's ``alpha = exp(−inf) = 0`` rescale wipes exactly, and a fully
masked *trailing* block contributes ``p = exp(−inf) = 0`` exactly — so
skipped-vs-visited produces identical bits (tests/test_kernels.py).
``skip_window_blocks=False`` keeps the dense grid for that comparison.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _window_lo_block(qi, *, q_offset, window, bq, bk):
    """First KV block that can intersect the query block's window span
    ``(q_offset + qi*bq − window, q_offset + (qi+1)*bq − 1]``."""
    return jnp.maximum(0, (q_offset + qi * bq - window + 1) // bk)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, bq, bk, q_offset, n_k_steps, skip):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    if skip:
        # windowed grid: step j visits KV block lo(qi) + j; kpos below
        # uses the UNCLAMPED index, so steps the index map clamped to the
        # last block land beyond the causal frontier and mask to exactly
        # zero weight (module docstring)
        ki = _window_lo_block(qi, q_offset=q_offset, window=window,
                              bq=bq, bk=bk) + ki

    @pl.when(pl.program_id(2) == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = jnp.ones((bq, bk), bool)
    if causal:
        valid &= kpos <= qpos
    if window is not None:
        valid &= (qpos - kpos) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "q_offset", "interpret",
                                             "skip_window_blocks"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None, bq=128,
                           bk=128, q_offset=0, interpret=None,
                           skip_window_blocks=True):
    """q: (BH, Sq, d); k, v: (BKV, Skv, d), BH = BKV * G. -> (BH, Sq, d).

    With ``causal`` + ``window`` the KV grid covers only the blocks a
    query block's window can reach (module docstring);
    ``skip_window_blocks=False`` restores the dense grid (identical
    bits, more steps — kept for the parity test and as the fallback for
    non-causal windows)."""
    BH, Sq, d = q.shape
    BKV, Skv, _ = k.shape
    assert BH % BKV == 0
    G = BH // BKV
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0
    n_k = Skv // bk
    scale = 1.0 / math.sqrt(d)

    from jax.experimental.pallas import tpu as pltpu

    # window + bq − 1 positions can span at most ceil(.../bk) + 1 blocks
    n_vis = n_k
    if causal and window is not None and skip_window_blocks:
        n_vis = min(n_k, -(-(window + bq - 1) // bk) + 1)
    skip = n_vis < n_k

    def kv_index(b, i, j, g=G):
        if not skip:
            return (b // g, j, 0)
        lo = _window_lo_block(i, q_offset=q_offset, window=window,
                              bq=bq, bk=bk)
        return (b // g, jnp.minimum(lo + j, n_k - 1), 0)

    grid = (BH, Sq // bq, n_vis)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, q_offset=q_offset, n_k_steps=n_vis,
                          skip=skip),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
