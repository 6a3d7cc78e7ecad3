"""The offloaded-inference engine (paper §3.3 system design).

Drives interactive (batch-1) autoregressive generation of an MoE model
under the paper's full algorithm:

* per-layer **LRU cache** of ``k`` experts (``core/lru_cache``),
* **speculative prefetch** of the lookahead layer's likely experts from the
  current layer's hidden state (``core/speculative``),
* **mixed quantization**: experts at 2/3-bit HQQ, shared layers at 4-bit
  (``quant/hqq``),
* byte-accurate transfer accounting (contiguous per-expert buffers — one
  copy per expert, matching the paper's pinned-buffer design).

Two execution modes (DESIGN.md §3/§6):

* **accounting** (``quantized=False``): the model decodes normally and
  the engine replays its routing decisions through ``PyLRU`` — offloading
  as *pure scheduling*, so generated tokens are bit-identical to plain
  decoding (tested).  This is the trace/ablation mode behind the Fig-2 /
  Table-2 benchmarks.
* **packed** (``quantized=True``, the default for quantized engines):
  expert weights stay HQQ-packed in a host-side store and stream through
  a per-layer device buffer pool of ``cache_size`` slots, driven by the
  jit-compatible LRU state machine (``core/lru_cache.access_plan`` /
  ``stage_plan`` decide the slot swaps, ``core/expert_pool`` performs
  them).  MoE compute reads the packed slots directly
  (``models/moe.moe_apply_packed`` -> ``kernels/ops.dequant_matmul``).
  Generated tokens are bit-identical to decoding the dequantized model
  (tested), transfer byte counts are *measured* packed copies, and no
  dense expert stack is ever materialized outside per-slot dequant.

``PyLRU`` and the jit state machine are property-tested equal — including
the eviction sequence — in
``tests/test_lru.py::test_jnp_matches_python_oracle``.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, OffloadSpec, parse_block
from repro.core import cost_model, expert_pool as EP, speculative
from repro.core.lru_cache import PyLRU
from repro.core.trace import moe_positions, stacked_routers
from repro.models import transformer as T
from repro.quant import hqq
from repro.runtime import Executor
from repro.serving.sampler import SamplerConfig, sample


@dataclass
class OffloadStats:
    n_tokens: int = 0
    hits: int = 0
    spec_hits: int = 0
    demand_loads: int = 0
    spec_loads: int = 0
    expert_bytes: float = 0.0  # per expert (quantized)

    @property
    def accesses(self) -> int:
        return self.hits + self.spec_hits + self.demand_loads

    @property
    def hit_ratio(self) -> float:
        return (self.hits + self.spec_hits) / max(1, self.accesses)

    def per_token(self) -> cost_model.TokenStats:
        n = max(1, self.n_tokens)
        return cost_model.TokenStats(
            demand_loads=self.demand_loads / n,
            spec_loads=self.spec_loads / n,
            hits=self.hits / n,
            spec_hits=self.spec_hits / n,
        )

    @property
    def bytes_h2d(self) -> float:
        return (self.demand_loads + self.spec_loads) * self.expert_bytes


# ----------------------------------------------------------------------
def routing_from_info(cfg: ModelConfig, info_stack, want_hiddens=True):
    """Unpack one ``decode_step(..., collect_info=True)`` result into
    layer-major per-MoE-layer routing: returns (ids, hiddens) lists of
    length n_moe_layers with arrays (B, top_k) int32 and (B, D)
    (``hiddens`` is empty with ``want_hiddens=False``, skipping that
    device->host transfer for callers that only count expert ids).

    This is the single decode-side source of routing truth, shared by the
    offload accounting below and by the serving scheduler's expert-overlap
    policy (``serving/scheduler.ExpertOverlapPolicy``).
    """
    ids, hiddens = [], []
    for per in range(cfg.n_periods):
        for i in range(cfg.pattern_period):
            info = info_stack[i]
            if "route" not in info:
                continue
            ids.append(np.asarray(info["route"]["ids"][per]))
            if want_hiddens:
                hiddens.append(np.asarray(info["hidden_pre_moe"][per]))
    return ids, hiddens


class ExpertUsageTracker:
    """Decayed per-MoE-layer histogram of expert activations.

    Tracks which experts the in-flight batch has recently routed to —
    i.e. what the offload engine's per-layer caches are hot with.  The
    continuous-batching admission policy scores waiting requests by
    overlap with this histogram (MoBiLE-style expert-aware grouping:
    admitting requests that reuse the already-loaded experts amortises
    expert-load cost on memory-constrained hardware).
    """

    def __init__(self, n_layers: int, n_experts: int, decay: float = 0.9):
        self.n_layers = n_layers
        self.n_experts = n_experts
        self.decay = decay
        self.counts = np.zeros((n_layers, n_experts), np.float64)

    @classmethod
    def for_config(cls, cfg: ModelConfig, decay: float = 0.9
                   ) -> "ExpertUsageTracker":
        n = len(moe_positions(cfg)) * cfg.n_periods
        return cls(n, cfg.moe.num_experts, decay)

    def update(self, ids_per_layer, rows=None) -> None:
        """ids_per_layer: list of (B, K) int32 (from ``routing_from_info``);
        ``rows`` restricts accounting to the active batch rows."""
        self.counts *= self.decay
        for l, ids in enumerate(ids_per_layer):
            sel = ids if rows is None else ids[np.asarray(rows, np.int64)]
            np.add.at(self.counts[l], np.asarray(sel).ravel(), 1.0)

    def normalized(self) -> np.ndarray:
        """(L, E) rows summing to 1 (uniform when a layer has no counts)."""
        tot = self.counts.sum(-1, keepdims=True)
        uniform = np.full_like(self.counts, 1.0 / self.n_experts)
        return np.where(tot > 0, self.counts / np.maximum(tot, 1e-9), uniform)

    def overlap(self, pred_ids_per_layer) -> float:
        """Score a candidate's predicted expert set against the in-flight
        histogram: expected fraction of its expert hits already hot.
        Normalized by the layers actually scored — a candidate supplying
        more prediction lists than the tracker holds layers must not have
        its score deflated by the unscored surplus."""
        hist = self.normalized()
        score = 0.0
        scored = pred_ids_per_layer[: self.n_layers]
        for l, ids in enumerate(scored):
            score += float(hist[l, np.asarray(ids, np.int64).ravel()].sum())
        return score / max(1, len(scored))


# ----------------------------------------------------------------------
def quantize_for_offload(params, cfg: ModelConfig, spec: OffloadSpec, *,
                         pack_experts: bool = False, telemetry=None):
    """Mixed quantization of the model (paper §3.3): experts at
    ``spec.expert_bits``, attention/shared weights at ``spec.attn_bits``;
    embeddings / router / norms stay 16-bit.

    By default returns ``(exec_params, size_report)`` with every
    quantized weight eagerly dequantized back to dense — this is the
    *parity oracle* (what a dequantize-then-matmul execution computes),
    NOT the memory-saving path; ``size_report`` carries the true packed
    sizes.

    With ``pack_experts=True`` expert weights are never dequantized:
    returns ``(exec_params, size_report, store)`` where ``store`` is the
    packed host store (``core/expert_pool.build_store``, bitwise the same
    quantization as the oracle path) and ``exec_params`` carries
    zero-size placeholders for the expert stacks — the packed engine
    below computes MoE straight from the store/pool, so no dense expert
    tensor exists to materialize.  ``size_report["experts"]`` is then the
    measured store size.  The store is built in bounded slabs of each
    expert matrix stack, so that the build needs little device memory
    beside the weights it reads (at Mixtral-8x7B's widths the float32
    copy of one whole stack would not fit on the chip); ``telemetry``
    receives one ``offload.build`` span per slab.
    """
    qsizes = {"experts": 0, "attn": 0, "fp16": 0}
    dtype = jnp.dtype(cfg.dtype)
    store = (EP.build_store(params, cfg, spec, telemetry)
             if pack_experts else None)

    def quant_leaf(path, leaf, bits):
        if leaf.ndim < 2:
            qsizes["fp16"] += leaf.size * 2
            return leaf
        name = path[-1]
        if "experts" in path:
            mat = leaf.reshape(-1, *leaf.shape[-2:])  # (E, K, N)
        elif name in ("wq", "wk", "wv"):
            mat = leaf.reshape(leaf.shape[0], -1)  # (D, H*hd)
        elif name == "wo":
            mat = leaf.reshape(-1, leaf.shape[-1])  # (H*hd, D)
        else:
            mat = leaf
        gs = hqq.PAPER_SCHEMES[bits]["group_size"]
        if mat.shape[-2] % gs:
            qsizes["fp16"] += leaf.size * 2
            return leaf
        qt = hqq.quantize(mat, bits)
        key = "experts" if "experts" in path else "attn"
        qsizes[key] += hqq.nbytes(qt)
        return hqq.dequantize(qt, dtype).reshape(leaf.shape)

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(walk(v, path + (str(i),))
                              for i, v in enumerate(tree))
        name = path[-1]
        if "experts" in path:
            if pack_experts:
                # weights live packed in the host store; leave a zero-size
                # placeholder so the param tree keeps its structure (and
                # nothing dense can be computed with by accident)
                return jnp.zeros(tree.shape[:1] + (0,), tree.dtype)
            return quant_leaf(path, tree, spec.expert_bits)
        if name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                    "w_in", "w_out"):
            return quant_leaf(path, tree, spec.attn_bits)
        qsizes["fp16"] += tree.size * 2
        return tree

    exec_params = walk(params, ())
    if pack_experts:
        qsizes["experts"] = store.nbytes()
    qsizes["total"] = qsizes["experts"] + qsizes["attn"] + qsizes["fp16"]
    if pack_experts:
        return exec_params, qsizes, store
    return exec_params, qsizes


# ----------------------------------------------------------------------
def PackedDecoder(params, cfg: ModelConfig, spec: OffloadSpec, store,
                  *, fused: bool = True, pipelined: bool = True,
                  vectorized: bool = True) -> Executor:
    """Compat constructor for the pre-runtime layerwise packed decoder:
    the block programs now live in :class:`repro.runtime.Executor`
    (DESIGN.md §8) — this returns a packed-plane executor with the same
    ``decode`` / ``prefill`` / ``init_pool_state`` surface and the same
    cached program keys."""
    plane = "packed_pipelined" if pipelined else "packed_vectorized"
    return Executor(params, cfg, plane=plane, spec=spec, store=store,
                    fused=fused, vectorized=vectorized)


# ----------------------------------------------------------------------
class OffloadEngine:
    """Stateful wrapper around one model + offload configuration.

    ``quantized=False`` — accounting mode (pure scheduling, PyLRU replay).
    ``quantized=True``  — packed mode: real HQQ-packed execution through
    the device buffer pool (module docstring).  ``packed=False`` opts a
    quantized engine back into accounting over the eagerly-dequantized
    model (the parity oracle the packed mode is tested against).
    """

    def __init__(self, params, cfg: ModelConfig,
                 spec: Optional[OffloadSpec] = None, quantized: bool = False,
                 *, packed: Optional[bool] = None, fused: bool = True,
                 pipelined: bool = True, vectorized: bool = True,
                 telemetry=None, draft=None, num_draft_tokens: int = 0):
        assert cfg.moe is not None, "offloading targets MoE architectures"
        self.cfg = cfg
        self.spec = spec or cfg.offload or OffloadSpec()
        self.size_report = None
        self.packed = bool(quantized) if packed is None else bool(packed)
        if self.packed and not quantized:
            raise ValueError("packed execution requires quantized=True "
                             "(the store holds HQQ-packed experts)")
        self.store = None
        self._decoder = None
        self._last_pool_state = None
        from repro.obs import Telemetry, jit_cache_metrics
        self.obs = telemetry if telemetry is not None else Telemetry.off()
        self.build_s = 0.0  # seconds spent quantizing and packing
        if quantized:
            t0 = time.perf_counter()
            if self.packed:
                params, self.size_report, self.store = quantize_for_offload(
                    params, cfg, self.spec, pack_experts=True,
                    telemetry=self.obs)
            else:
                params, self.size_report = quantize_for_offload(
                    params, cfg, self.spec)
            jax.block_until_ready((params, self.store))
            self.build_s = time.perf_counter() - t0
        self.store_bytes = 0 if self.store is None else self.store.nbytes()
        self.params = params
        self.routers = stacked_routers(params, cfg)  # (L_moe, D, E)
        self.n_moe_layers = self.routers.shape[0]
        if self.packed:
            # packed planes of the unified runtime (DESIGN.md §8)
            self._decoder = PackedDecoder(params, cfg, self.spec, self.store,
                                          fused=fused, pipelined=pipelined,
                                          vectorized=vectorized)
            self._exec = self._decoder
            # measured: what one demand load / prefetch actually copies
            self.expert_bytes = EP.per_expert_nbytes(self.store)
        else:
            self._exec = Executor(params, cfg)
            eff_bits = cost_model.EFFECTIVE_BITS[
                self.spec.expert_bits if quantized else 16]
            self.expert_bytes = (cost_model.expert_param_count(cfg)
                                 * eff_bits / 8.0)
        # live routing histogram, readable by serving-admission policies
        self.usage = ExpertUsageTracker(self.n_moe_layers,
                                        cfg.moe.num_experts)
        # telemetry plane (DESIGN.md §10): cumulative transfer accounting
        # feeds the offload collector; each generate() traces its
        # prefill/decode spans
        self.last_stats: Optional[OffloadStats] = None
        self._cum = OffloadStats(expert_bytes=self.expert_bytes)
        self.obs.registry.register_collector("offload", self._offload_metrics)
        self.obs.registry.register_collector("jit", jit_cache_metrics)
        self._gen_count = 0
        # token-level draft-and-verify (DESIGN.md §11): engine-level
        # defaults; generate(draft=, num_draft_tokens=) overrides
        self.draft = draft
        self.num_draft_tokens = int(num_draft_tokens or 0)
        self._spec_metrics = None
        if self.draft is not None and self.num_draft_tokens >= 1:
            self._ensure_spec_metrics()
        if self.obs.timing:
            self.obs.declare_request_schema()
            self._exec.set_observer(self.obs.exec_observer(self._exec.plane))

    # ------------------------------------------------------------------
    def _ensure_spec_metrics(self):
        if self._spec_metrics is None:
            from repro.obs import SpecMetrics
            self._spec_metrics = SpecMetrics(self.obs.registry)
        return self._spec_metrics

    # ------------------------------------------------------------------
    def _offload_metrics(self):
        """Telemetry ``offload`` namespace: cumulative across generates
        (the same numbers every returned :class:`OffloadStats` carries —
        ``benchmarks/offload_bench.py`` asserts the two never drift)."""
        c = self._cum
        return {"hits": c.hits, "spec_hits": c.spec_hits,
                "demand_loads": c.demand_loads, "spec_loads": c.spec_loads,
                "bytes_h2d": c.bytes_h2d,
                "bytes_per_token": c.bytes_h2d / max(1, c.n_tokens),
                "store_bytes": self.store_bytes, "build_s": self.build_s}

    def _record_generate(self, stats: OffloadStats) -> None:
        """Fold one generate()'s measured stats into the telemetry plane."""
        self.last_stats = stats
        c = self._cum
        c.n_tokens += stats.n_tokens
        c.hits += stats.hits
        c.spec_hits += stats.spec_hits
        c.demand_loads += stats.demand_loads
        c.spec_loads += stats.spec_loads

    def metrics(self):
        """Namespaced telemetry snapshot (``repro.obs.schema``)."""
        return self.obs.snapshot()

    # ------------------------------------------------------------------
    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 greedy: bool = True, rng=None,
                 sampler: Optional[SamplerConfig] = None, *,
                 prefill_chunk: Optional[int] = None, draft=None,
                 num_draft_tokens: Optional[int] = None
                 ) -> Tuple[np.ndarray, OffloadStats]:
        """prompt: (1, S) int32.  Returns (generated (1, n), stats).

        Packed engines really perform the slot swaps (stats are measured
        copies); accounting engines replay routing through PyLRU.  All
        sampling routes through ``serving/sampler.py``: ``greedy=False``
        is shorthand for a plain categorical :class:`SamplerConfig`, and
        ``sampler=`` overrides (top-k / top-p / temperature).  ``rng``
        may be omitted, in which case a fixed seeded key makes sampled
        runs reproducible.  ``prefill_chunk`` chunks the prompt's prefill
        (bitwise-identical to whole-prompt prefill on every plane —
        DESIGN.md §8).

        ``draft``/``num_draft_tokens`` (defaulting to the engine-level
        settings; explicit ``num_draft_tokens=0`` disables) switch greedy
        decode to draft-and-verify speculation (DESIGN.md §11) — bitwise
        identical output, several tokens per verify chunk."""
        sampler = sampler or SamplerConfig(
            kind="greedy" if greedy else "categorical")
        if sampler.kind != "greedy" and rng is None:
            rng = jax.random.key(0)  # seeded default, not a crash in split
        draft = self.draft if draft is None else draft
        k = self.num_draft_tokens if num_draft_tokens is None \
            else int(num_draft_tokens)
        if draft is not None and k >= 1:
            if sampler.kind != "greedy":
                raise ValueError("draft-and-verify speculation is greedy "
                                 "decoding only (DESIGN.md §11)")
            return self._generate_speculative(
                prompt, max_new_tokens, draft, k,
                prefill_chunk=prefill_chunk)
        if self._decoder is not None:
            return self._generate_packed(prompt, max_new_tokens,
                                         sampler=sampler, rng=rng,
                                         prefill_chunk=prefill_chunk)
        cfg, spec = self.cfg, self.spec
        caches = [PyLRU(spec.cache_size, spec.num_speculative)
                  for _ in range(self.n_moe_layers)]
        stats = OffloadStats(expert_bytes=self.expert_bytes)

        obs = self.obs
        rid = self._gen_count
        self._gen_count += 1
        obs.req_submitted(rid, rid)
        obs.req_admitted(rid, 0)
        t_pre = obs.clock_ns() if obs.tracer is not None else 0
        max_len = prompt.shape[1] + max_new_tokens
        pre_logits, state, _ = self._exec.prefill(
            jnp.asarray(prompt), max_len, chunk=prefill_chunk)
        obs.req_chunk(rid, 0, int(prompt.shape[1]), t_pre)
        # prefill loads each layer once (paper: the encode phase "works
        # relatively well with existing algorithms"); generation-phase
        # accounting starts below.  First token comes from prefill logits.
        rng, tok = self._next_token(rng, pre_logits, sampler)
        out = [int(tok[0, 0])]
        obs.req_decode_start(rid)
        for step_i in range(max_new_tokens - 1):
            logits, state, _, (info_stack, _) = self._exec.decode(
                state, tok, collect_info=True)
            self._account(info_stack, caches, stats)
            stats.n_tokens += 1
            rng, tok = self._next_token(rng, logits, sampler)
            out.append(int(tok[0, 0]))
        for c in caches:
            stats.hits += c.hits
            stats.spec_hits += c.spec_hits
            stats.demand_loads += c.demand
            stats.spec_loads += c.spec_loads
        self._record_generate(stats)
        obs.req_finished(rid, len(out), "length")
        return np.asarray(out)[None], stats

    # ------------------------------------------------------------------
    def _next_token(self, rng, logits, sampler: SamplerConfig):
        """One sampler step over the last-position logits -> (rng', tok
        (B, 1) int32).  Greedy keeps the on-device argmax (no rng)."""
        if sampler.kind == "greedy":
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        else:
            rng, sub = jax.random.split(rng)
            nxt = sample(sub, logits[:, -1], sampler)
        return rng, nxt[:, None].astype(jnp.int32)

    # ------------------------------------------------------------------
    def _generate_packed(self, prompt: np.ndarray, max_new_tokens: int,
                         sampler: SamplerConfig, rng=None,
                         prefill_chunk: Optional[int] = None
                         ) -> Tuple[np.ndarray, OffloadStats]:
        """Packed-execution generate: prefill streams the routed experts
        from the host store chunk by chunk (``moe_apply_packed_stream``,
        no pool traffic); every decode token is served from the device
        buffer pool with the LRU/speculative machinery performing real
        slot swaps (DESIGN.md §6/§8)."""
        dec = self._decoder
        pstate = dec.init_pool_state()
        obs = self.obs
        rid = self._gen_count
        self._gen_count += 1
        obs.req_submitted(rid, rid)
        obs.req_admitted(rid, 0)
        t_pre = obs.clock_ns() if obs.tracer is not None else 0
        max_len = prompt.shape[1] + max_new_tokens
        pre_logits, state, _ = dec.prefill(jnp.asarray(prompt), max_len,
                                           chunk=prefill_chunk)
        obs.req_chunk(rid, 0, int(prompt.shape[1]), t_pre)
        rng, tok = self._next_token(rng, pre_logits, sampler)
        out = [int(tok[0, 0])]
        obs.req_decode_start(rid)
        for _ in range(max_new_tokens - 1):
            logits, state, pstate, route_ids = dec.decode(state, tok, pstate)
            self.usage.update([np.asarray(i) for i in route_ids])
            rng, tok = self._next_token(rng, logits, sampler)
            out.append(int(tok[0, 0]))
        counts = np.asarray(pstate.counts)
        stats = OffloadStats(
            n_tokens=max_new_tokens - 1,
            hits=int(counts[0]), spec_hits=int(counts[1]),
            demand_loads=int(counts[2]), spec_loads=int(counts[3]),
            expert_bytes=self.expert_bytes)
        self._last_pool_state = pstate  # inspectable by tests/examples
        self._record_generate(stats)
        obs.req_finished(rid, len(out), "length")
        return np.asarray(out)[None], stats

    # ------------------------------------------------------------------
    def _generate_speculative(self, prompt: np.ndarray, max_new_tokens: int,
                              draft, k: int, *,
                              prefill_chunk: Optional[int] = None
                              ) -> Tuple[np.ndarray, OffloadStats]:
        """Draft-and-verify greedy decode (DESIGN.md §11).

        Per round the draft proposes ``k_eff = min(k, remaining−1)``
        tokens; the target verifies them in ONE ``C = k_eff+1`` chunk
        through :meth:`Executor.decode` (one pool acquire per MoE layer
        per chunk), accepts the longest matching prefix plus its own
        next token, then rolls back: the target KV rollback is a pos
        reset only — ring/page entries past ``pos`` are dead under the
        attention validity mask and get overwritten when real tokens
        land at the same positions.  The invariant ``pos = S + n − 1``
        (n tokens emitted) holds at every round boundary, which is what
        makes the output bitwise identical to non-speculative greedy:
        each chunk position's argmax conditions on exactly the canonical
        prefix as long as every earlier draft token matched."""
        from repro.core.draft import verify_round
        # rollback is a pos reset, which only works while the KV ring has
        # never wrapped: a wrapped SWA ring would have rejected verify-
        # chunk writes overwrite the live entry `window` positions back
        win = self.cfg.sliding_window
        if (win and any(parse_block(b)[0] == "swa"
                        for b in self.cfg.block_pattern)
                and int(prompt.shape[1]) + max_new_tokens > win):
            raise ValueError(
                f"speculative decoding needs the request inside the SWA "
                f"window ({int(prompt.shape[1])} + {max_new_tokens} > "
                f"window={win}): a wrapped ring cannot roll back rejected "
                f"verify chunks")
        packed = self._decoder is not None
        dec = self._exec
        pstate = dec.init_pool_state() if packed else None
        caches = None if packed else [
            PyLRU(self.spec.cache_size, self.spec.num_speculative)
            for _ in range(self.n_moe_layers)]
        stats = OffloadStats(expert_bytes=self.expert_bytes)
        spec_m = self._ensure_spec_metrics()
        obs = self.obs
        rid = self._gen_count
        self._gen_count += 1
        obs.req_submitted(rid, rid)
        obs.req_admitted(rid, 0)
        t_pre = obs.clock_ns() if obs.tracer is not None else 0
        S = int(prompt.shape[1])
        max_len = S + max_new_tokens
        pre_logits, state, _ = dec.prefill(jnp.asarray(prompt), max_len,
                                           chunk=prefill_chunk)
        obs.req_chunk(rid, 0, S, t_pre)
        out = [int(jnp.argmax(pre_logits[0, -1]))]
        # the draft's KV ring needs k extra positions of headroom: after
        # a rejection it has fed itself up to k−1 tokens past the stream
        draft.start(np.asarray(prompt), max_len + k)
        prompt_list = [int(t) for t in np.asarray(prompt).reshape(-1)]
        obs.req_decode_start(rid)

        def _one_step(tok):
            nonlocal state, pstate
            if packed:
                logits, state, pstate, route_ids = dec.decode(
                    state, tok, pstate)
                self.usage.update([np.asarray(i) for i in route_ids])
            else:
                logits, state, _, (info_stack, _) = dec.decode(
                    state, tok, collect_info=True)
                self._account(info_stack, caches, stats)
            return logits

        while len(out) < max_new_tokens:
            k_eff = min(k, max_new_tokens - len(out) - 1)
            if k_eff < 1:
                # last token: a plain C=1 step
                logits = _one_step(jnp.asarray([[out[-1]]], jnp.int32))
                stats.n_tokens += 1
                out.append(int(jnp.argmax(logits[0, -1])))
                continue
            canon = prompt_list + out
            d = draft.propose(canon[draft.consumed:], k_eff)
            chunk = np.concatenate(
                [[out[-1]], np.asarray(d)]).astype(np.int32)[None]
            logits = _one_step(jnp.asarray(chunk))
            tgt = np.asarray(jnp.argmax(logits[0], -1))  # (k_eff+1,)
            emitted, a = verify_round(d, tgt)
            out.extend(emitted)
            stats.n_tokens += len(emitted)
            # target KV rollback: pos reset to the canonical frontier
            state = dict(state, pos=jnp.full_like(state["pos"],
                                                  S + len(out) - 1))
            draft.accept(a)
            spec_m.round(k_eff, a)

        if packed:
            counts = np.asarray(pstate.counts)
            stats.hits = int(counts[0])
            stats.spec_hits = int(counts[1])
            stats.demand_loads = int(counts[2])
            stats.spec_loads = int(counts[3])
            self._last_pool_state = pstate
        else:
            for c in caches:
                stats.hits += c.hits
                stats.spec_hits += c.spec_hits
                stats.demand_loads += c.demand
                stats.spec_loads += c.spec_loads
        spec_m.add_bytes(stats.bytes_h2d)
        self._record_generate(stats)
        obs.req_finished(rid, len(out), "length")
        return np.asarray(out)[None], stats

    # ------------------------------------------------------------------
    def _account(self, info_stack, caches: List[PyLRU], stats: OffloadStats):
        """Feed one decode chunk's routing decisions to the cache
        machinery, position by position, layer by layer.  Expert staging
        (prefetch for l+j fires while 'computing' layer l) runs only for
        C = 1 steps — the same ``T == 1`` gate the packed planes apply,
        so a C = k+1 speculative verify chunk never stages on either
        execution mode (DESIGN.md §11)."""
        spec = self.spec
        ids, hiddens = routing_from_info(self.cfg, info_stack)
        self.usage.update(ids)
        n_pos = int(ids[0].shape[0]) if ids else 1
        for t in range(n_pos):
            for l in range(self.n_moe_layers):
                caches[l].access(ids[l][t])
                tgt = l + spec.lookahead
                if n_pos == 1 and tgt < self.n_moe_layers:
                    pred = speculative.predict_experts(
                        jnp.asarray(self.routers[tgt]),
                        jnp.asarray(hiddens[l][t])[None],
                        spec.num_speculative)
                    caches[tgt].stage(np.asarray(pred[0]))

    # ------------------------------------------------------------------
    def throughput_estimate(self, stats: OffloadStats, hw_name: str) -> float:
        hw = cost_model.HARDWARE[hw_name]
        bits = self.spec.expert_bits if self.size_report else 16
        return cost_model.tokens_per_second(self.cfg, hw, stats.per_token(),
                                            bits, self.spec.attn_bits)


# ----------------------------------------------------------------------
def generate_plain(params, cfg: ModelConfig, prompt: np.ndarray,
                   max_new_tokens: int, *,
                   prefill_chunk: Optional[int] = None,
                   extras=None) -> np.ndarray:
    """Greedy decode without any offload bookkeeping (parity oracle).

    Dispatches through the plain plane of the unified runtime
    (DESIGN.md §8): prompt prefill is the C = S case of the chunked
    block program — every engine that must match this oracle bitwise
    (continuous batching, packed offloading) runs the very same
    programs, and ``prefill_chunk`` splits the prompt without changing
    a single output bit.  Works for every layer kind in the config zoo
    (DESIGN.md §12); enc-dec archs pass
    ``extras={"audio_embeds": ...}``."""
    ex = Executor(params, cfg)
    return ex.generate_greedy(prompt, max_new_tokens,
                              prefill_chunk=prefill_chunk, extras=extras)
