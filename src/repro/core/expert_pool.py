"""Packed expert weights: host-side store + per-layer device buffer pool.

This is the data plane of the paper's offloading system (DESIGN.md §6).
Expert weights are HQQ-quantized once and then *stay packed*:

* :class:`PackedExperts` — one triple of stacked :class:`~repro.quant.hqq.QTensor`
  (``w_gate``/``w_up``/``w_down``).  The same container describes all three
  residency tiers, distinguished only by its leading axes:

  - **host store**  ``(L_moe, E, ...)`` — every expert of every MoE layer.
    Today a device-resident array on every backend (no ``memory_kind``
    placement: moving it to pinned host memory is ROADMAP R1), so the h2d
    transfers the counters report are computed, not moved.  Never
    dequantized as a whole.
  - **LRU pool**    ``(L_moe, cache_size, ...)`` — the per-layer device
    buffer pool of ``k`` expert slots the paper keeps resident.
  - **staging**     ``(L_moe, num_speculative, ...)`` — the speculative
    prefetch buffers ("the newly loaded experts do not replace the
    currently cached experts").

* :class:`PoolState` — the jit-carried mutable state: the stacked LRU
  state machine (``core/lru_cache``), both buffer tiers, and the transfer
  counters.

* :func:`acquire` — serve one layer's routed experts: the LRU state
  machine (:func:`~repro.core.lru_cache.access_plan`) decides slots and
  byte sources, and this function *performs* the implied swaps —
  host-store gathers for demand misses, staging→pool promotion for
  speculative hits — returning the packed slot contents the MoE kernel
  computes with (``models/moe.moe_apply_packed``).

* :func:`stage` — speculative prefetch into the lookahead layer's staging
  buffers (:func:`~repro.core.lru_cache.stage_plan` decides which
  predictions cost a host transfer vs a device-local copy).

Everything below is pure/jittable; the slot index of ``cache_ids`` in the
LRU state IS the pool slot index, so the state machine and the buffers
cannot drift apart.
"""
from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, OffloadSpec, parse_block
from repro.core import lru_cache as LC
from repro.quant import hqq

EXPERT_MATS = ("w_gate", "w_up", "w_down")

# Fault-injection site name for the h2d fetch this module's ``acquire``
# performs (DESIGN.md §14).  ``acquire`` itself is jit-pure — it cannot
# consult a host-side injector — so the executor's per-layer Python loop
# injects AT this boundary: a fired fault means "the gather h2d for this
# layer's routed experts failed", and the executor retries or degrades
# to store-direct streaming (``models/moe.moe_apply_packed_stream``)
# without ever entering the pool path for that layer.
FAULT_SITE = "expert_fetch"


class PackedExperts(NamedTuple):
    """Stacked packed expert weights (see module docstring for tiers)."""

    w_gate: hqq.QTensor
    w_up: hqq.QTensor
    w_down: hqq.QTensor

    @property
    def n_layers(self) -> int:
        return self.w_gate.shape[0]

    @property
    def n_slots(self) -> int:
        """Second leading axis: E for the store, k/n_spec for the pools."""
        return self.w_gate.shape[1]

    def slice(self, *idx) -> "PackedExperts":
        return PackedExperts(*(hqq.slice_leading(qt, idx) for qt in self))

    def nbytes(self) -> int:
        return sum(hqq.nbytes(qt) for qt in self)


class PoolState(NamedTuple):
    """Jit-carried offload state (donated through the decode loop)."""

    lru: LC.LayerCacheState   # leaves stacked (L_moe, ...)
    pool: PackedExperts       # (L_moe, cache_size, ...)
    staging: PackedExperts    # (L_moe, num_speculative, ...)
    counts: jnp.ndarray  # (4,) i32: hits, spec_hits, demand, spec_loads


# ----------------------------------------------------------------------
# construction
# Bound on the float32 copy of one slab of an expert matrix stack while
# the store is built.  The seeded bfloat16 weights are still on the device
# then: at Mixtral-8x7B's widths four layers take 12.1 GB of a v5e's
# 16 GiB and their packed store 2.1 GB more, which leaves ~1.5 GB for a
# slab, its bfloat16 and float32 copies and HQQ's codes and scales (HQQ's
# program itself needs no temporaries beyond them on a v5e).  One
# 4096 x 14336 expert matrix is 235 MB in float32, so a slab then holds
# one; Granite's 2 MiB matrices go 128 to a slab.  A slab holds at least
# one matrix, whatever its size.
SLAB_F32_BYTES = 256 << 20


@partial(jax.jit, static_argnums=2)
def _slab(leaf, start, n):
    """Matrices ``start .. start + n`` of a (P, E, K, N) stack as one
    (n, K, N) array, without a copy of the whole stack."""
    flat = leaf.reshape((-1,) + leaf.shape[-2:])
    return jax.lax.dynamic_slice_in_dim(flat, start, n)


@partial(jax.jit, static_argnums=0)
def _join(lead, parts):
    """The slabs' leaves concatenated along the matrix axis, which then
    takes the ``lead`` axes."""
    return jax.tree.map(
        lambda *xs: jnp.concatenate(xs).reshape(lead + xs[0].shape[1:]),
        *parts)


def _quantize_stack(leaf, bits: int, span) -> hqq.QTensor:
    """HQQ-quantize a (P, E, K, N) expert matrix stack in slabs of at most
    ``SLAB_F32_BYTES`` of float32 along its P*E matrix axis, each slab
    finished before the next is begun.  HQQ and its meta-quantization
    group along K inside each matrix, so the codes are those of one call
    on the whole stack.  ``span(start, n)``: a context around a slab's
    quantization (``None`` or a span whose ``args`` take its ``bytes``)."""
    lead, (K, N) = leaf.shape[:-2], leaf.shape[-2:]
    total = math.prod(lead)
    per = max(1, SLAB_F32_BYTES // (K * N * 4))
    parts, qt = [], None
    for start in range(0, total, per):
        n = min(per, total - start)
        with span(start, n) as sp:
            qt = hqq.quantize(_slab(leaf, start, n), bits)
            jax.block_until_ready(qt)
            if sp is not None:
                sp.args["bytes"] = hqq.nbytes(qt)
        parts.append((qt.packed, qt.scale, qt.zero, qt.meta))
    packed, scale, zero, meta = _join(tuple(lead), parts)
    return hqq.QTensor(packed, scale, zero, meta, qt.bits, qt.group_size,
                       tuple(leaf.shape))


def build_store(params, cfg: ModelConfig, spec: OffloadSpec,
                telemetry=None) -> PackedExperts:
    """Quantize every MoE layer's experts into the layer-major packed host
    store, bitwise the same quantization ``quantize_for_offload`` applies
    before its (oracle-only) dequantization — the packed-execution parity
    invariant rests on this.

    Each matrix stack is quantized in slabs of at most ``SLAB_F32_BYTES``
    of float32 (:func:`_quantize_stack`): at Mixtral-8x7B's widths the
    float32 copy of a whole stack would not fit on the chip beside the
    weights it is built from.  With ``telemetry`` each slab is an
    ``offload.build`` span, with args ``matrix``, ``layer`` (the layer
    it starts in), ``experts`` (the matrices in it) and ``bytes`` (their
    packed size).
    """
    assert cfg.moe is not None, "packed store targets MoE architectures"
    if any(parse_block(k)[1] == "moe" for k in cfg.tail_kinds()):
        raise ValueError("packed offloading supports fully-scanned MoE "
                         "stacks (no MoE tail layers)")
    pos = [i for i, k in enumerate(cfg.block_pattern)
           if parse_block(k)[1] == "moe"]
    gs = hqq.PAPER_SCHEMES[spec.expert_bits]["group_size"]
    P = cfg.n_periods
    per_pos = []
    for j, p in enumerate(pos):
        leafs = params["stack"][p]["moe"]["experts"]
        mats = {}
        for name in EXPERT_MATS:
            leaf = leafs[name]          # (P, E, K, N)
            K, E = leaf.shape[-2], leaf.shape[1]
            if K % gs:
                raise ValueError(
                    f"packed offloading needs expert contraction dims "
                    f"divisible by the {spec.expert_bits}-bit group size "
                    f"{gs}; got {name} with K={K}")

            def span(start, n, name=name, E=E, j=j):
                if telemetry is None:
                    return contextlib.nullcontext()
                # execution order is period-major over the MoE positions
                return telemetry.span("offload.build", matrix=name,
                                      layer=(start // E) * len(pos) + j,
                                      experts=n)
            mats[name] = _quantize_stack(leaf, spec.expert_bits, span)
        per_pos.append(mats)

    def layer_major(name):
        # execution order is period-major over the pattern's MoE positions
        qts = [m[name] for m in per_pos]
        E = qts[0].shape[1]
        tail = qts[0].shape[2:]
        L = P * len(qts)
        if len(qts) == 1:
            src = qts[0]
            leaves = (src.packed, src.scale, src.zero)
            meta = src.meta
        else:
            st = lambda f: jnp.stack([getattr(q, f) for q in qts], axis=1)
            leaves = (st("packed"), st("scale"), st("zero"))
            meta = None if qts[0].meta is None else \
                {k: jnp.stack([q.meta[k] for q in qts], axis=1)
                 for k in qts[0].meta}
        nlead = 1 if len(qts) == 1 else 2
        r = lambda a: a.reshape((L, E) + a.shape[nlead + 1:])
        meta = None if meta is None else {k: r(v) for k, v in meta.items()}
        return hqq.QTensor(r(leaves[0]), r(leaves[1]), r(leaves[2]), meta,
                           qts[0].bits, qts[0].group_size, (L, E) + tail)

    return PackedExperts(*(layer_major(n) for n in EXPERT_MATS))


def init_pool_state(store: PackedExperts, spec: OffloadSpec) -> PoolState:
    """Zero-filled buffer pool + staging tier + cold LRU state for a store."""
    L = store.n_layers

    def tier(n_slots: int) -> PackedExperts:
        def zqt(qt: hqq.QTensor) -> hqq.QTensor:
            z = lambda a: jnp.zeros((L, n_slots) + a.shape[2:], a.dtype)
            meta = None if qt.meta is None else \
                {k: z(v) for k, v in qt.meta.items()}
            return hqq.QTensor(z(qt.packed), z(qt.scale), z(qt.zero), meta,
                               qt.bits, qt.group_size,
                               (L, n_slots) + qt.shape[2:])
        return PackedExperts(*(zqt(qt) for qt in store))

    return PoolState(
        lru=LC.init_model_state(L, spec.cache_size, spec.num_speculative),
        pool=tier(spec.cache_size),
        staging=tier(spec.num_speculative),
        counts=jnp.zeros((4,), jnp.int32),
    )


def per_expert_nbytes(store: PackedExperts) -> float:
    """Measured packed bytes of ONE expert (all three matrices) — what a
    demand load or speculative prefetch actually copies host->device."""
    return store.nbytes() / (store.n_layers * store.n_slots)


# ----------------------------------------------------------------------
# jit-side slot plumbing
def _qt_where(pred, a: hqq.QTensor, b: hqq.QTensor) -> hqq.QTensor:
    w = lambda x, y: jnp.where(pred, x, y)
    meta = None if a.meta is None else \
        {k: w(a.meta[k], b.meta[k]) for k in a.meta}
    return hqq.QTensor(w(a.packed, b.packed), w(a.scale, b.scale),
                       w(a.zero, b.zero), meta, a.bits, a.group_size,
                       a.shape)


def _qt_set(qt: hqq.QTensor, l, s, sub: hqq.QTensor) -> hqq.QTensor:
    u = lambda a, v: a.at[l, s].set(v)
    meta = None if qt.meta is None else \
        {k: u(qt.meta[k], sub.meta[k]) for k in qt.meta}
    return hqq.QTensor(u(qt.packed, sub.packed), u(qt.scale, sub.scale),
                       u(qt.zero, sub.zero), meta, qt.bits, qt.group_size,
                       qt.shape)


def _pe_set(pe: PackedExperts, l, s, sub: PackedExperts) -> PackedExperts:
    return PackedExperts(*(_qt_set(qt, l, s, sq)
                           for qt, sq in zip(pe, sub)))


def _pe_where(pred, a: PackedExperts, b: PackedExperts) -> PackedExperts:
    return PackedExperts(*(_qt_where(pred, x, y) for x, y in zip(a, b)))


def qt_stack(qts) -> hqq.QTensor:
    """Stack homogeneous QTensors along a new leading axis."""
    st = lambda xs: jnp.stack(xs)
    q0 = qts[0]
    meta = None if q0.meta is None else \
        {k: st([q.meta[k] for q in qts]) for k in q0.meta}
    return hqq.QTensor(st([q.packed for q in qts]),
                       st([q.scale for q in qts]),
                       st([q.zero for q in qts]), meta,
                       q0.bits, q0.group_size, (len(qts),) + q0.shape)


def pe_stack(pes) -> PackedExperts:
    return PackedExperts(*(qt_stack([getattr(p, n) for p in pes])
                           for n in EXPERT_MATS))


def _qt_gather(qt: hqq.QTensor, l, idx: jnp.ndarray) -> hqq.QTensor:
    """Gather ``idx`` (n,) slices of layer ``l`` from a (L, S, ...) stacked
    QTensor as ONE indexed read per leaf — the vectorized replacement for
    n sequential ``slice_leading`` + ``qt_stack`` round trips."""
    g = lambda a: a[l, idx]
    meta = None if qt.meta is None else {k: g(v) for k, v in qt.meta.items()}
    return hqq.QTensor(g(qt.packed), g(qt.scale), g(qt.zero), meta,
                       qt.bits, qt.group_size,
                       (idx.shape[0],) + tuple(qt.shape[2:]))


def pe_gather(pe: PackedExperts, l, idx: jnp.ndarray) -> PackedExperts:
    """(L, S, ...) tier -> (n, ...) gathered slices at ``idx`` (n,)."""
    return PackedExperts(*(_qt_gather(qt, l, idx) for qt in pe))


def _qt_where_rows(mask: jnp.ndarray, a: hqq.QTensor, b: hqq.QTensor
                   ) -> hqq.QTensor:
    """Row-wise select between two (n, ...) stacked QTensors; ``mask`` is
    (n,) bool, broadcast over each leaf's trailing axes."""
    def w(x, y):
        m = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)
    meta = None if a.meta is None else \
        {k: w(a.meta[k], b.meta[k]) for k in a.meta}
    return hqq.QTensor(w(a.packed, b.packed), w(a.scale, b.scale),
                       w(a.zero, b.zero), meta, a.bits, a.group_size,
                       a.shape)


def pe_where_rows(mask, a: PackedExperts, b: PackedExperts) -> PackedExperts:
    return PackedExperts(*(_qt_where_rows(mask, x, y) for x, y in zip(a, b)))


def _pe_set_row(pe: PackedExperts, l, mask: jnp.ndarray,
                new_row: PackedExperts) -> PackedExperts:
    """Write layer ``l``'s whole (S, ...) row of a tier in one update,
    keeping old contents where ``mask`` (S,) is False."""
    def upd(qt: hqq.QTensor, sub: hqq.QTensor) -> hqq.QTensor:
        def u(a, v):
            m = mask.reshape(mask.shape + (1,) * (v.ndim - 1))
            return a.at[l].set(jnp.where(m, v, a[l]))
        meta = None if qt.meta is None else \
            {k: u(qt.meta[k], sub.meta[k]) for k in qt.meta}
        return hqq.QTensor(u(qt.packed, sub.packed), u(qt.scale, sub.scale),
                           u(qt.zero, sub.zero), meta, qt.bits,
                           qt.group_size, qt.shape)
    return PackedExperts(*(upd(qt, sq) for qt, sq in zip(pe, new_row)))


# ----------------------------------------------------------------------
def acquire(store: PackedExperts, st: PoolState, l, ids: jnp.ndarray,
            active: Optional[jnp.ndarray] = None, *,
            vectorized: bool = True) -> Tuple[PoolState, PackedExperts]:
    """Serve layer ``l``'s routed experts ``ids`` (T, K) from its buffer
    pool, performing the slot swaps the LRU state machine decides.

    Returns ``(st', served)`` where ``served`` holds the packed weights
    each (token, k) pair computes with, stacked ``(T*K, ...)`` leading —
    captured *at access time*, so a later eviction within the same batch
    cannot corrupt an earlier token's weights.

    ``active`` (T,) bool masks rows whose output is discarded (free slots
    of a continuous-batching batch): they bypass the cache entirely —
    weights straight from the host store, no state change, no accounting.

    ``vectorized`` (default) performs all swaps as one batched
    gather/scatter over the whole-batch plan (DESIGN.md §7);
    ``vectorized=False`` is the PR-2 per-(token, k) sequential data plane,
    kept as the measured baseline of ``benchmarks/offload_bench.py``.
    Both are bitwise-identical (tested).
    """
    if vectorized:
        return _acquire_vectorized(store, st, l, ids, active)
    return _acquire_unrolled(store, st, l, ids, active)


def _acquire_vectorized(store: PackedExperts, st: PoolState, l,
                        ids: jnp.ndarray,
                        active: Optional[jnp.ndarray] = None
                        ) -> Tuple[PoolState, PackedExperts]:
    """One-gather/one-scatter data plane (DESIGN.md §7).

    The state machine plans the whole batch (:func:`~repro.core.lru_cache.
    access_plan_batch`); the pool row is then rewritten in ONE masked
    scatter — every written slot receives the store bytes of the expert
    the final LRU table says lives there, which is exactly what the
    sequential swap sequence leaves behind (slot contents are a function
    of the final ``cache_ids``, the coherence invariant §6 tests) — and
    the served weights come from ONE batched gather: pool slots for
    accesses that survive the batch, host store for the rest (bitwise
    identical either way, since a pool slot always holds its expert's
    store bytes).
    """
    T, K = ids.shape
    lru = LC.layer_slice(st.lru, l)
    new_lru, delta, plan = LC.access_plan_batch(lru, ids, active)
    # scatter: rewrite the written pool slots from the store in one update
    safe_ids = jnp.clip(new_lru.cache_ids, 0, store.n_slots - 1)
    pool = _pe_set_row(st.pool, l, plan.written,
                       pe_gather(store, l, safe_ids))
    # gather: serve every access from its pool slot when it survived the
    # batch, else from the store (access-time capture)
    flat = ids.reshape(T * K)
    from_pool = pe_gather(pool, l, plan.slots.reshape(T * K))
    from_store = pe_gather(store, l, flat)
    served = pe_where_rows(plan.survives.reshape(T * K),
                           from_pool, from_store)
    st = PoolState(LC.set_layer(st.lru, l, new_lru), pool, st.staging,
                   st.counts + delta)
    return st, served


def _acquire_unrolled(store: PackedExperts, st: PoolState, l,
                      ids: jnp.ndarray,
                      active: Optional[jnp.ndarray] = None
                      ) -> Tuple[PoolState, PackedExperts]:
    """PR-2 sequential data plane: T*K full-tensor where/set updates plus
    a ``pe_stack`` of per-access weight copies.  Kept (unused by the
    engines) as the synchronous baseline ``benchmarks/offload_bench.py``
    measures the vectorized plane against."""
    T, K = ids.shape
    lru = LC.layer_slice(st.lru, l)
    pool, staging = st.pool, st.staging
    counts = st.counts
    served = []
    for t in range(T):
        act = None if active is None else active[t]
        new_lru, stats, plan = LC.access_plan(lru, ids[t])
        for j in range(K):
            from_store = store.slice(l, ids[t, j])
            from_pool = pool.slice(l, plan.slots[j])
            from_stag = staging.slice(l, plan.spec_slot[j])
            content = _pe_where(
                plan.in_cache[j], from_pool,
                _pe_where(plan.in_spec[j], from_stag, from_store))
            if act is not None:
                content = _pe_where(act, content, from_store)
                write = _pe_where(act, content, from_pool)
            else:
                write = content
            pool = _pe_set(pool, l, plan.slots[j], write)
            served.append(content)
        delta = jnp.stack([stats.hits, stats.spec_hits, stats.demand_loads,
                           jnp.zeros((), jnp.int32)])
        if act is not None:
            new_lru = jax.tree.map(lambda n, o: jnp.where(act, n, o),
                                   new_lru, lru)
            delta = jnp.where(act, delta, 0)
        lru = new_lru
        counts = counts + delta
    st = PoolState(LC.set_layer(st.lru, l, lru), pool, staging, counts)
    return st, pe_stack(served)


def stage(store: PackedExperts, st: PoolState, tgt, predicted: jnp.ndarray,
          valid, *, vectorized: bool = True) -> PoolState:
    """Stage ``predicted`` (n_spec,) experts into layer ``tgt``'s staging
    buffers (the paper's speculative prefetch, fired while the current
    layer computes).  ``valid`` gates the whole update (False when the
    lookahead runs past the last MoE layer).  Buffer contents are sourced
    per :func:`~repro.core.lru_cache.stage_plan`: residents copy
    device-locally (pool slot / previous staging buffer), everything else
    streams from the host store — only those count as transfers.

    ``vectorized`` (default) fills the whole staging row with one gather
    (DESIGN.md §7); ``vectorized=False`` is the PR-2 per-buffer loop,
    kept for the offload benchmark's baseline.  Bitwise identical: every
    staged buffer ends up holding its prediction's store bytes whichever
    resident tier the sequential plane copies them from.
    """
    n_spec = predicted.shape[0]
    if n_spec == 0:
        return st
    L = store.n_layers
    tgt_c = jnp.clip(tgt, 0, L - 1)
    lru = LC.layer_slice(st.lru, tgt_c)
    new_lru, plan, transfers = LC.stage_plan(lru, predicted)
    if vectorized:
        # one gather fills the whole staging row with the predictions'
        # store bytes (== whatever resident tier the sequential plane
        # would have copied them from)
        fill = pe_gather(store, tgt_c,
                         jnp.clip(predicted, 0, store.n_slots - 1))
        mask = jnp.broadcast_to(jnp.asarray(valid), (n_spec,))
        staging = _pe_set_row(st.staging, tgt_c, mask, fill)
    else:
        old_staging = st.staging  # pre-update contents: sources intact
        staging = st.staging
        for j in range(n_spec):
            content = _pe_where(
                plan.in_cache[j], st.pool.slice(tgt_c, plan.cache_slot[j]),
                _pe_where(plan.in_old_spec[j],
                          old_staging.slice(tgt_c, plan.old_spec_slot[j]),
                          store.slice(tgt_c, predicted[j])))
            keep = old_staging.slice(tgt_c, j)
            staging = _pe_set(staging, tgt_c, j,
                              _pe_where(valid, content, keep))
    new_lru = jax.tree.map(lambda n, o: jnp.where(valid, n, o), new_lru, lru)
    counts = st.counts + jnp.where(valid, transfers, 0) * \
        jnp.asarray([0, 0, 0, 1], jnp.int32)
    return PoolState(LC.set_layer(st.lru, tgt_c, new_lru), st.pool,
                     staging, counts)
