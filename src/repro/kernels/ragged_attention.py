"""Ragged, page-aware decode attention over block-paged KV (DESIGN.md §9).

The paged KV plane stores every layer's KV in a shared pool of fixed-size
pages — ``kp/vp: (P, page_size, Hkv, hd)`` with per-page absolute
positions ``ppos: (P, page_size)`` (−1 = never written) — and each batch
row owns an ordered *page table* row ``pages: (B, max_pages)`` (−1 =
unallocated).  Logical position ``p`` of a row lives at page
``pages[b, p // page_size]``, offset ``p % page_size``.  This module is
the attention read side of that layout, in two tiers:

* :func:`ragged_attention_reference` — the CPU/tier-1 fallback: gathers
  the rows' pages into a dense ``(B, max_pages*page_size)`` KV view and
  runs the model's own ``attention_core`` on it.  Because the gathered
  view reproduces the ring layout index-for-index (position ``p`` at
  index ``p``; unallocated slots carry ``kpos = −1`` exactly like empty
  ring slots), its output is **bitwise identical** to the dense path at
  matched width — the engines' paged mode exercises the same semantics
  the pre-paged tests froze (tests/test_paged_kv.py).  Cost scales with
  the *table width it is handed*: callers slice the table to the live
  page horizon (``serving.kv_manager.PagedKVManager.live_width``) so
  decode attention pays for live context, not slot capacity.

* :func:`ragged_attention_pallas` — the accelerator kernel: a flat
  *work list* of (row, page) pairs rides in as scalar-prefetch arrays
  (the ``dequant_matmul_slots`` pattern) and **is** the grid — pages
  beyond a row's live length or wholly outside the sliding window are
  never visited, so per-step attention work is O(total live pages), per
  row, not O(batch × table width).  Online softmax runs in VMEM scratch
  with accumulators reset/flushed at each row's first/last work item.

:func:`build_page_worklist` derives the kernel's work list host-side
from the page tables + per-row query spans; its length is the kernel's
grid size and the quantity ``benchmarks/attention_bench.py`` shows
scaling with live tokens.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


# ----------------------------------------------------------------------
# Dense gather + reference (the tier-1 fallback path)
def ragged_gather(kp, vp, ppos, pages, layer=None):
    """Gather each row's pages into a dense KV view.

    kp/vp: (P, ps, Hkv, hd); ppos: (P, ps); pages: (B, T) int32 page ids
    (−1 = unallocated).  Returns (k, v, kpos) with k/v (B, T*ps, Hkv, hd)
    and kpos (B, T*ps); entries under unallocated table slots carry
    kpos = −1 (their k/v values are whatever page 0 holds — masked out of
    every attention exactly like empty ring slots).

    ``layer`` reads layer-stacked pools — kp/vp (L, P, ps, Hkv, hd) —
    through ONE fused gather, so a scanned decode step never slices a
    whole layer's pool out of its carry (that copy is what made paged
    cost scale with pool size instead of live pages; DESIGN.md §9).
    """
    B, T = pages.shape
    pidc = jnp.maximum(pages, 0)                       # (B, T)
    if layer is None:
        k = kp[pidc]                                   # (B, T, ps, Hkv, hd)
        v = vp[pidc]
        kpos = jnp.where(pages[:, :, None] >= 0, ppos[pidc], -1)
    else:
        k = kp[layer, pidc]
        v = vp[layer, pidc]
        kpos = jnp.where(pages[:, :, None] >= 0, ppos[layer, pidc], -1)
    ps = k.shape[2]
    return (k.reshape(B, T * ps, *k.shape[3:]),
            v.reshape(B, T * ps, *v.shape[3:]),
            kpos.reshape(B, T * ps))


def ragged_attention_reference(q, kp, vp, ppos, pages, qpos, *,
                               window: Optional[int] = None,
                               q_chunk: Optional[int] = None, layer=None):
    """Blockwise (page-gather) reference: bitwise the model's
    ``attention_core`` over the gathered dense view (module docstring).

    q: (B, C, H, hd); qpos: (B, C) int32 absolute query positions.
    """
    from repro.models.layers import attention_core  # lazy: layers imports us
    k, v, kpos = ragged_gather(kp, vp, ppos, pages, layer=layer)
    return attention_core(q, k, v, qpos, kpos, causal=True, window=window,
                          q_chunk=q_chunk or q.shape[1])


# ----------------------------------------------------------------------
# Host-side work-list construction (the kernel's grid)
def build_page_worklist(pages, n_live, q_lo, q_hi, page_size: int, *,
                        window: Optional[int] = None,
                        pad_to: Optional[int] = None
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flatten (row, page) work for one ragged decode/chunk step.

    pages: (B, T) int page tables; n_live[b]: live token count of row b
    (0 = row idle — emits no work); queries of row b sit at absolute
    positions ``[q_lo[b], q_hi[b]]``.  A page is listed only if it holds
    a position ``<= q_hi`` (causal / live-length skip) and, with a
    sliding ``window``, a position ``> q_lo − window`` (window skip) —
    the two grid-level skips the dense path pays masking for.

    Returns (wrow, wpage, wflags) int32 arrays of equal length (padded
    to ``pad_to`` with inert entries); wflags[:, 0/1/2] = first/last/
    valid.  The un-padded length is the kernel's real work — the
    quantity that scales with live tokens.
    """
    pages = np.asarray(pages)
    n_live = np.asarray(n_live)
    q_lo = np.broadcast_to(np.asarray(q_lo), (pages.shape[0],))
    q_hi = np.broadcast_to(np.asarray(q_hi), (pages.shape[0],))
    B, T = pages.shape
    wrow, wpage, wflags = [], [], []
    for b in range(B):
        n_pages = -(-int(n_live[b]) // page_size)  # ceil
        keep = []
        for o in range(min(n_pages, T)):
            pid = int(pages[b, o])
            if pid < 0:
                continue
            page_lo, page_hi = o * page_size, (o + 1) * page_size - 1
            if page_lo > q_hi[b]:
                continue  # wholly beyond the causal frontier
            if window is not None and page_hi <= q_lo[b] - window:
                continue  # wholly outside the sliding window
            keep.append(pid)
        for j, pid in enumerate(keep):
            wrow.append(b)
            wpage.append(pid)
            wflags.append((int(j == 0), int(j == len(keep) - 1), 1))
    n = len(wrow)
    pad_to = max(pad_to or n, n, 1)
    # inert padding repeats the LAST real (row, page) pair: a pad step
    # revisits a block whose VMEM already holds that row's finalized
    # output, so the compiled kernel's block writeback is a no-op.
    # Padding with (0, 0) would instead revisit row 0's output block
    # without writing it and flush stale scratch over it on TPU.
    pr, pp = (wrow[-1], wpage[-1]) if n else (0, 0)
    while len(wrow) < pad_to:
        wrow.append(pr)
        wpage.append(pp)
        wflags.append((0, 0, 0))
    return (np.asarray(wrow, np.int32), np.asarray(wpage, np.int32),
            np.asarray(wflags, np.int32).reshape(pad_to, 3))


# ----------------------------------------------------------------------
# Pallas kernel: the work list IS the grid
def _ragged_kernel(wrow_ref, wpage_ref, wflags_ref,
                   qpos_ref, q_ref, kp_ref, vp_ref, ppos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, window, n_groups):
    i = pl.program_id(0)
    first = wflags_ref[i, 0]
    last = wflags_ref[i, 1]
    valid = wflags_ref[i, 2]

    @pl.when(first == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(valid == 1)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale      # (C, H, hd)
        k = kp_ref[0].astype(jnp.float32)             # (ps, Hkv, hd)
        v = vp_ref[0].astype(jnp.float32)
        kpos = ppos_ref[0, 0]                         # (ps,)
        C, H, hd = q.shape
        Hkv = k.shape[1]
        qg = q.reshape(C, Hkv, n_groups, hd)
        s = jnp.einsum("chgd,thd->chgt", qg, k,
                       preferred_element_type=jnp.float32)  # (C,Hkv,G,ps)
        qp = qpos_ref[0, 0]                           # (C,) this row's qpos
        ok = (kpos[None, :] >= 0) & (kpos[None, :] <= qp[:, None])
        if window is not None:
            ok &= (qp[:, None] - kpos[None, :]) < window
        s = jnp.where(ok[:, None, None, :], s, NEG_INF).reshape(C, H, -1)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])             # (C, H, ps)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
        pv = jnp.einsum("chgt,thd->chgd",
                        p.reshape(C, Hkv, n_groups, -1), v,
                        preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha[..., None] + \
            pv.reshape(C, H, hd)
        m_scr[...] = m_new

    @pl.when(last == 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0] = (acc_scr[...] / l[..., None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def ragged_attention_pallas(q, kp, vp, ppos, qpos, wrow, wpage, wflags, *,
                            window: Optional[int] = None, interpret=None):
    """q: (B, C, H, hd) against paged KV via a (row, page) work list.

    The work list arrays ride in as scalar-prefetch arguments; the grid
    has ONE step per listed page — skipped pages (beyond live length /
    outside the window, see :func:`build_page_worklist`) cost nothing.
    Rows that contribute no work items keep undefined output (callers
    mask them — they are the engines' idle slots).
    """
    from jax.experimental.pallas import tpu as pltpu

    B, C, H, hd = q.shape
    P, ps, Hkv, _ = kp.shape
    assert H % Hkv == 0
    n_work = wrow.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_work,),
        in_specs=[
            pl.BlockSpec((1, 1, C), lambda i, wr, wp, wf: (wr[i], 0, 0)),
            pl.BlockSpec((1, C, H, hd),
                         lambda i, wr, wp, wf: (wr[i], 0, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, hd),
                         lambda i, wr, wp, wf: (wp[i], 0, 0, 0)),
            pl.BlockSpec((1, ps, Hkv, hd),
                         lambda i, wr, wp, wf: (wp[i], 0, 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda i, wr, wp, wf: (wp[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, H, hd),
                               lambda i, wr, wp, wf: (wr[i], 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((C, H), jnp.float32),
            pltpu.VMEM((C, H), jnp.float32),
            pltpu.VMEM((C, H, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, scale=1.0 / (hd ** 0.5),
                          window=window, n_groups=H // Hkv),
        out_shape=jax.ShapeDtypeStruct((B, C, H, hd), q.dtype),
        grid_spec=grid_spec,
        interpret=resolve_interpret(interpret),
        # qpos is a blocked input, not a scalar-prefetch one: Mosaic cannot
        # read a vector row out of a 2-D SMEM array.  qpos/ppos get a unit
        # middle axis because Mosaic tiles a block's last two dims: a
        # (1, n) row block of a 2-D array is refused, (1, 1, n) is whole
    )(wrow.astype(jnp.int32), wpage.astype(jnp.int32),
      wflags.astype(jnp.int32), qpos.astype(jnp.int32)[:, None, :], q, kp,
      vp, ppos[:, None, :])


# ----------------------------------------------------------------------
def ragged_attention(q, kp, vp, ppos, pages, qpos, *,
                     window: Optional[int] = None,
                     q_chunk: Optional[int] = None,
                     worklist=None, layer=None):
    """Dispatch: with a host-built ``worklist`` (wrow, wpage, wflags)
    run the Pallas page-skip kernel; inside jitted model programs (no
    host work list) the gather reference runs — on every backend that is
    the served path today, and it is bitwise ``attention_core``."""
    if worklist is not None:
        assert layer is None, "worklist kernel takes per-layer pools"
        wrow, wpage, wflags = worklist
        return ragged_attention_pallas(q, kp, vp, ppos, qpos,
                                       jnp.asarray(wrow), jnp.asarray(wpage),
                                       jnp.asarray(wflags), window=window)
    return ragged_attention_reference(q, kp, vp, ppos, pages, qpos,
                                      window=window, q_chunk=q_chunk,
                                      layer=layer)
