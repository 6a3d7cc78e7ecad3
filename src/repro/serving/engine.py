"""Serving engines (non-offloaded accelerator path).

Two modes, both dispatching through the unified runtime
(:class:`repro.runtime.Executor` — DESIGN.md §8; no engine owns a
private copy of the block-step bodies):

* :class:`ServeEngine` — static batch: left-pads a fixed request set to a
  common length and decodes until every request finishes.  Pad positions
  are excluded from attention and from MoE dispatch capacity via the
  ``pad_mask`` threaded through the padded prefill (DESIGN.md §2).
* :class:`ContinuousEngine` — continuous batching: requests join and
  leave a *running* batch (DESIGN.md §4).  A slotted KV state
  (``serving/kv_manager``) holds ``max_slots`` sequences at independent
  positions; admission prefill runs through the executor's chunk program
  (B=1, the whole prompt as one chunk by default — bitwise identical to
  the ``generate_plain`` oracle, which runs the same program), and
  finished requests release their slot the same step.  Which waiting
  request joins next is the scheduler policy's call (expert-overlap
  grouping, ``serving/scheduler``).

  With ``prefill_chunk=C`` admission becomes **chunked prefill**
  (DESIGN.md §8): each step executes a :class:`~repro.runtime.StepPlan`
  mixing one decode token per running row with prompt chunks packed
  under a :class:`~repro.runtime.TokenBudgetPolicy` — a long prompt no
  longer head-of-line-blocks the in-flight decodes.  Chunking never
  changes a *logit* bit, so under greedy decoding the generated tokens
  are bitwise those of unchunked admission (tests/test_runtime.py);
  stochastic samplers stay distribution-identical but consume the
  engine rng stream in a different step order, so sampled streams are
  reproducible per seed, not across chunk settings.

The memory-constrained interactive mode is
``core/offload_engine.OffloadEngine`` (the paper's contribution).
:class:`ContinuousEngine` composes with it: passing a packed offload
engine (``offload=...``) switches decode to the HQQ-packed expert
buffer pool — continuous batching over offloaded experts, with the pool
shared across the running batch (DESIGN.md §6) and prefill chunks
streaming their experts straight from the host store (zero pool
traffic, DESIGN.md §8).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, parse_block
from repro.core.offload_engine import (ExpertUsageTracker, routing_from_info)
from repro.data.pipeline import EOS
from repro.obs import Telemetry, jit_cache_metrics
from repro.runtime import (Admission, ChunkTask, Executor, StepPlan,
                           TokenBudgetPolicy)
from repro.serving.faults import SITES as FAULT_SITES
from repro.serving.kv_manager import KVSlotManager, StateManager
from repro.serving.sampler import SamplerConfig, sample
from repro.serving.scheduler import (RUNNING, GenRequest, Scheduler,
                                     admission_cost)


@dataclass
class Request:
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 32
    completed: List[int] = field(default_factory=list)


@dataclass
class _Swapped:
    """A preempted request parked off-device (DESIGN.md §13): either its
    pages staged to host (``blob``) or dropped for recompute resume."""

    req: GenRequest
    blob: Optional[dict]  # host-staged pages; None => recompute resume
    next_tok: int         # pending (already emitted) token to feed
    n_tokens: int         # live KV positions at preemption
    seq: int              # FIFO tie-break within a priority class


class ServeEngine:
    def __init__(self, params, cfg: ModelConfig,
                 sampler: Optional[SamplerConfig] = None):
        self.params = params
        self.cfg = cfg
        self.sampler = sampler or SamplerConfig(kind="greedy")
        self._exec = Executor(params, cfg)

    def serve_batch(self, requests: List[Request], seed: int = 0
                    ) -> List[Request]:
        """Left-pads prompts to a common length and decodes the batch.
        The pad mask keeps shorter prompts from attending to (or spending
        MoE capacity on) pad positions; each row decodes from its own
        true length (per-row ``pos``).  Pad isolation only exists for
        causal-attention stacks — recurrent mixers fold pad tokens into
        their state, so unequal-length batches are rejected there."""
        cfg = self.cfg
        B = len(requests)
        S = max(len(r.prompt) for r in requests)
        needs_pad = any(len(r.prompt) != S for r in requests)
        if needs_pad and not cfg.attention_only_stack:
            raise ValueError(
                f"left-padded serve_batch needs a causal-attention stack; "
                f"{cfg.name}'s mixers accumulate state over pad tokens "
                f"— batch equal-length prompts for this arch")
        max_new = max(r.max_new_tokens for r in requests)
        toks = np.zeros((B, S), np.int32)
        mask = np.zeros((B, S), bool)
        for i, r in enumerate(requests):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad with 0
            mask[i, S - len(r.prompt):] = True
        batch = {"tokens": jnp.asarray(toks)}
        if needs_pad:
            batch["pad_mask"] = jnp.asarray(mask)
        pre_logits, state = self._exec.prefill_padded(batch, S + max_new)
        rng = jax.random.key(seed)
        rng, sub = jax.random.split(rng)
        tok = sample(sub, pre_logits[:, -1], self.sampler)
        done = np.zeros(B, bool)
        for i in range(B):
            requests[i].completed.append(int(tok[i]))
        for step in range(max_new - 1):
            logits, state, _, _ = self._exec.decode(state, tok[:, None])
            rng, sub = jax.random.split(rng)
            tok = sample(sub, logits[:, -1], self.sampler)
            for i, r in enumerate(requests):
                if done[i] or len(r.completed) >= r.max_new_tokens:
                    done[i] = True
                    continue
                t = int(tok[i])
                r.completed.append(t)
                if t == EOS:
                    done[i] = True
            if done.all():
                break
        return requests


# ======================================================================
class ContinuousEngine:
    """Continuous-batching decode loop over a slotted KV state.

    Per step: (1) admit policy-selected waiting requests into free slots
    — whole-prompt prefill (one chunk) by default, budgeted prompt
    chunks with ``prefill_chunk=C`` — (2) one batched executor decode
    step over the running slots with per-row positions, (3) sample
    (through ``serving/sampler`` with per-request temperatures), stream
    tokens to request callbacks, evict finished requests.  Free slots
    decode a dummy token whose output is ignored and whose state is
    fully overwritten at the next admission.
    """

    def __init__(self, params, cfg: ModelConfig, *, max_slots: int = 4,
                 slot_len: int = 256, sampler: Optional[SamplerConfig] = None,
                 policy=None, eos_id: Optional[int] = EOS,
                 prefill_chunk: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 seed: int = 0, offload=None,
                 kv_page: Optional[int] = None,
                 kv_pages_total: Optional[int] = None,
                 ragged_bucket: bool = True,
                 prefix_cache_pages: int = 0,
                 preemption: bool = False,
                 kv_host_pages: int = 0,
                 telemetry: Optional[Telemetry] = None,
                 draft_params=None,
                 draft_cfg: Optional[ModelConfig] = None,
                 num_draft_tokens: int = 0,
                 faults=None,
                 check_invariants: bool = False,
                 queue_cap: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 fetch_retries: int = 2,
                 fetch_backoff_ms: float = 0.0):
        """``offload``: a packed :class:`~repro.core.offload_engine.
        OffloadEngine` (``quantized=True``) switches this engine into
        **offloaded decode mode** (DESIGN.md §6): experts stay HQQ-packed
        in the offload engine's host store, every decode step serves the
        batch's routed experts from the per-layer device buffer pool
        (shared across requests — the expert-overlap admission policy is
        what makes that sharing pay), and admission prefill streams
        experts from the host store chunk-wise.  ``params`` is ignored in
        that mode (the offload engine's executable params are used).

        ``prefill_chunk``: admission prompt chunk size; ``None`` = whole
        prompt per step (one chunk).  ``token_budget`` caps the tokens
        one step computes (decode rows + prefill chunks); default
        ``max_slots + prefill_chunk``.

        ``kv_page`` switches the KV plane to **block-paged storage**
        (DESIGN.md §9): KV lives in a shared pool of ``kv_pages_total``
        pages of ``kv_page`` positions (default: full provisioning,
        ``max_slots * ceil(slot_len/kv_page)``), requests reserve pages
        for their actual ``prompt + max_new`` instead of a slot_len
        ring, admission chunks write straight into the slot's pages (no
        install copy), and every decode step's attention is sliced to
        the live page horizon — cost follows live context, not slot
        width.  ``ragged_bucket=False`` pins the horizon to the full
        table, which makes paged decoding BITWISE the dense engine
        (tests/test_paged_kv.py); bucketing keeps greedy token streams
        identical while paying only for live pages.

        ``prefix_cache_pages`` (paged, causal-attention-only stacks):
        keep up to that many immutable full pages of finished prompts in
        a radix prefix index (``serving/prefix_cache``); a request whose
        prompt hits a cached prefix adopts those pages read-only and
        prefills only from the divergence point (DESIGN.md §13).  0
        disables the cache (a real ablation, not a falsy default).

        ``preemption`` (paged, causal-attention-only stacks): admission
        reserves only the prompt's pages instead of the worst case, and
        when decode growth or a higher-priority admission runs the pool
        dry a victim is *preempted* — its pages staged to a host pool of
        ``kv_host_pages`` pages (d2h) and re-staged on resume, or, when
        the host budget cannot hold them (``kv_host_pages=0`` always),
        dropped and rebuilt by re-prefilling prompt+generated.  Either
        resume path is bitwise the uninterrupted decode under greedy
        sampling.  Off by default: admission keeps the PR-5 no-
        preemption discipline and stalls until releases free pages.

        ``draft_params`` / ``draft_cfg`` / ``num_draft_tokens``: token-
        level draft-and-verify decoding (DESIGN.md §11).  With a dense
        draft model sharing the target's vocab and ``num_draft_tokens=k
        >= 1``, each step decodes every running row through one C =
        k+1 verify chunk instead of k+1 single-token steps: the draft
        proposes k tokens per row, the target verifies them in one
        chunk, the longest matching prefix plus the target's own next
        token is emitted, and both target KV (``truncate``) and draft
        state roll back past each row's rejection point.  Greedy
        sampler only; the output token streams are bitwise those of
        non-speculative decode for any draft.  ``num_draft_tokens=0``
        disables speculation regardless of the draft arguments (the
        CLI ablation path).

        ``telemetry``: a :class:`repro.obs.Telemetry` turns on the
        unified telemetry plane (DESIGN.md §10) — per-step phase spans,
        per-request and per-dispatch-site span tracing.  Default is
        ``Telemetry.off()``: only the pull-time collectors that back
        :meth:`metrics` / :meth:`stats` exist, the decode loop carries
        zero instrumentation, and generated tokens are bitwise identical
        either way (tests/test_obs.py).

        ``faults``: a seeded :class:`repro.serving.faults.FaultInjector`
        turns on the fault-injection plane (DESIGN.md §14): transient
        expert-fetch failures retry ``fetch_retries`` times (sleeping
        ``fetch_backoff_ms`` between attempts) then degrade to store-
        direct streaming; page-pool/swap faults exercise the admission
        and preemption stall paths; ``nan_logits`` poisons one decode
        row, which is quarantined (terminal status ``failed``) while
        every other row's tokens stay bitwise the fault-free run's.
        ``None`` (default) removes every injection check from the hot
        path.  ``check_invariants=True`` runs the full step-boundary
        accounting audit (:meth:`check_invariants`) after EVERY step.
        ``queue_cap`` bounds the admission queue — :meth:`submit` on a
        full queue returns a request already finished with terminal
        status ``rejected`` (backpressure, never unbounded growth).
        ``deadline_ms`` is the default per-request wall-clock budget;
        per-request ``deadline_ms`` / ``deadline_steps`` on submit()
        override it."""
        self.offload = offload
        if offload is not None:
            if offload._decoder is None:
                raise ValueError("offloaded decode mode needs a packed "
                                 "OffloadEngine (quantized=True)")
            if offload.cfg is not cfg and offload.cfg != cfg:
                raise ValueError("offload engine config mismatch")
            params = offload.params
            self._exec: Executor = offload._decoder
            self._pstate = self._exec.init_pool_state()
        else:
            self._exec = Executor(params, cfg)
        self.params = params
        self.cfg = cfg
        self.sampler = sampler or SamplerConfig(kind="greedy")
        self.max_slots = max_slots
        self.eos_id = eos_id
        self.paged = kv_page is not None
        # per-layer-kind state planes (DESIGN.md §12): the facade picks
        # dense rings vs paged KV for the growing "kv" layers; recurrent
        # layers keep fixed-size per-slot state either way and reserve
        # ZERO pool pages (the degenerate one-page-per-slot case)
        self.kv = StateManager.create(
            cfg, max_slots, slot_len, kv_page=kv_page,
            kv_pages_total=kv_pages_total, bucket=ragged_bucket)
        if self.paged:
            slot_len = self.kv.slot_len  # per-request cap, page-rounded
        self.slot_len = slot_len
        self.sched = Scheduler(max_slots, policy, queue_cap=queue_cap)
        # --------------------------------------------------------------
        # fault-injection plane + request-lifecycle hardening (§14)
        self.faults = faults
        self._check_inv = bool(check_invariants)
        self._deadline_ms = deadline_ms
        self._nan_quarantined = 0
        # executors can be shared (the offload engine hands over its
        # decoder) — like set_observer, the last engine to attach wins
        self._exec.set_fault_injector(faults, max_retries=fetch_retries,
                                      backoff_ms=fetch_backoff_ms)
        if hasattr(self.kv, "set_fault_injector"):
            self.kv.set_fault_injector(faults)
        # --------------------------------------------------------------
        # prefix reuse + preemption (DESIGN.md §13)
        self._prefix = None
        self._preempt = bool(preemption)
        self._swapped: List[_Swapped] = []
        self._swap_seq = 0
        self._recomputes = 0
        self._prefills_skipped = 0
        self._prefix_hit_tokens = 0
        if prefix_cache_pages or preemption or kv_host_pages:
            if not self.paged:
                raise ValueError(
                    "prefix caching / preemption need block-paged KV "
                    "(set kv_page); dense rings have no shareable or "
                    "swappable page unit")
            if not cfg.attention_only_stack:
                raise ValueError(
                    f"prefix caching / preemption need a causal-attention "
                    f"stack: {cfg.name!r} carries state (recurrent carries "
                    f"or encoder KV) that pages neither share nor swap")
            if kv_host_pages and not preemption:
                raise ValueError("kv_host_pages without preemption would "
                                 "never be used — enable preemption or "
                                 "drop the host pool")
            if preemption and num_draft_tokens:
                raise ValueError(
                    "preemption composes with plain decode only: a "
                    "draft-and-verify round holds un-verified KV that a "
                    "mid-round swap would tear")
        if prefix_cache_pages:
            from repro.serving.prefix_cache import PrefixCache
            self._prefix = PrefixCache(self.kv.page_size, prefix_cache_pages)
        if preemption:
            self.kv.enable_host_swap(kv_host_pages)
        self.prefill_chunk = prefill_chunk
        self.budget: Optional[TokenBudgetPolicy] = None
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            if prefill_chunk > slot_len:
                raise ValueError(f"prefill_chunk={prefill_chunk} exceeds "
                                 f"slot_len={slot_len} (the KV ring width)")
            self.budget = TokenBudgetPolicy(
                chunk_size=prefill_chunk,
                token_budget=token_budget or (max_slots + prefill_chunk),
                max_rows=max_slots)
        elif token_budget is not None:
            raise ValueError("token_budget needs prefill_chunk (the budget "
                             "schedules prompt chunks)")
        self._admissions: List[Admission] = []
        # routing collection costs per-step host transfers; only pay for
        # it when the admission policy actually reads the usage histogram
        # (the packed path surfaces routing for free)
        self._collect = (cfg.moe is not None
                         and (getattr(policy, "needs_usage", False)
                              or offload is not None))
        self.usage = (ExpertUsageTracker.for_config(cfg)
                      if self._collect else None)
        # greedy decode folds argmax into the jitted step and feeds the
        # token straight back on-device — the host only sees (B,) ints
        self._greedy = self.sampler.kind == "greedy"
        # request length cap: only GROWING kv planes consume positions.
        # All-SWA stacks roll their window inside the slot, so a request
        # may decode past slot_len; a pure-recurrent stack (xlstm) has
        # no growing plane at all, so NO request ever outgrows its slot.
        # Anything else must fit the slot ring; paged slots never roll
        # (pages are position-indexed), so every request must fit its
        # page reservation there.
        kv_mixers = {sp.mixer for sp in cfg.state_planes() if sp.grows}
        self._unbounded = (not self.paged
                           and (not kv_mixers
                                or (kv_mixers == {"swa"}
                                    and cfg.sliding_window
                                    and slot_len >= cfg.sliding_window)))
        self._has_rec = cfg.has_recurrent_layers
        self.tokens = np.zeros((max_slots, 1), np.int32)
        self.step_count = 0
        self._rng = jax.random.key(seed)
        # telemetry plane (DESIGN.md §10): collectors are registered even
        # in the off mode (they only run at snapshot time and back the
        # legacy stats() projection); timing/tracing attach only when an
        # enabled Telemetry is passed in
        self.obs = telemetry if telemetry is not None else Telemetry.off()
        reg = self.obs.registry
        reg.register_collector("engine", self._engine_metrics)
        reg.register_collector("kv", self.kv.metrics)
        reg.register_collector("jit", jit_cache_metrics)
        # always present — chaos and clean runs share one schema (all
        # fire counts are simply zero without an injector)
        reg.register_collector("faults", self._faults_metrics)
        if offload is not None:
            reg.register_collector("offload", self._offload_metrics)
        if self._prefix is not None:
            reg.register_collector("prefix", self._prefix_metrics)
        if self._preempt:
            reg.register_collector("kv_host", self._kv_host_metrics)
        if self.obs.timing:
            self.obs.declare_step_schema()
            self.obs.declare_request_schema()
        # dispatch-site spans (None with timing off); executors can be
        # shared (the offload engine hands over its decoder) — the last
        # engine to attach an observer wins
        self._xobs = self.obs.exec_observer(self._exec.plane)
        if self._xobs is not None:
            self._exec.set_observer(self._xobs)
        # --------------------------------------------------------------
        # token-level draft-and-verify decoding (DESIGN.md §11)
        self.spec_k = int(num_draft_tokens or 0)
        self._spec_metrics = None
        if self.spec_k > 0:
            if draft_params is None or draft_cfg is None:
                raise ValueError("num_draft_tokens >= 1 needs draft_params "
                                 "and draft_cfg (the dense draft model)")
            if not self._greedy:
                raise ValueError(
                    "draft-and-verify decoding is greedy-only: the "
                    "acceptance rule compares the target's argmax stream")
            if self._has_rec and (self.paged or offload is not None):
                raise ValueError(
                    f"draft-and-verify on {cfg.name!r} needs the dense "
                    f"non-offloaded engine: recurrent carries roll back "
                    f"by snapshot-and-restore of the pre-round row state, "
                    f"which the paged page-table trim and the packed "
                    f"offload step don't carry")
            # a wrapped ring cannot roll back: a rejected verify-chunk
            # write would overwrite the live entry W positions back.
            # Bound every request to the narrowest ring width instead of
            # letting SWA slots roll (dense rings are min(slot_len,
            # window) wide; paged KV is position-indexed and never
            # wraps, so its cap stays the page reservation)
            self._unbounded = False
            self._spec_cap = slot_len
            if (not self.paged and cfg.sliding_window
                    and any(parse_block(k)[0] == "swa"
                            for k in cfg.block_pattern)):
                self._spec_cap = min(slot_len, cfg.sliding_window)
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}; draft and target must share tokens")
            if not draft_cfg.attention_only_stack or draft_cfg.moe is not None:
                raise ValueError(
                    f"draft {draft_cfg.name!r} must be a dense causal-"
                    f"attention stack (rollback = pos reset; an MoE draft "
                    f"would compete for the h2d bus)")
            self._draft_exec = Executor(draft_params, draft_cfg)
            # draft ring gets k positions of headroom: rejected draft
            # self-feeds land past the canonical stream and must never
            # wrap onto live context
            self._draft_kv = KVSlotManager(draft_cfg, max_slots,
                                           slot_len + self.spec_k)
            self._draft_consumed = np.zeros(max_slots, np.int64)
            # which request's draft state each slot row holds — draft
            # admission is lazy (first speculative step touching the row)
            self._draft_rid = np.full(max_slots, -1, np.int64)
            from repro.obs import SpecMetrics
            self._spec_metrics = SpecMetrics(self.obs.registry)
            self._spec_last_h2d = 0.0

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32, on_token=None,
               on_finish=None, temperature: Optional[float] = None,
               extras: Optional[dict] = None,
               priority: int = 0,
               deadline_ms: Optional[float] = None,
               deadline_steps: Optional[int] = None) -> GenRequest:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size > 0, "empty prompt"
        if self._preempt:
            # optimistic admission must still terminate: a request whose
            # worst case exceeds the WHOLE pool could preempt every other
            # request and still deadlock mid-decode
            worst = self.kv.pool.pages_for(prompt.size + max_new_tokens)
            if worst > self.kv.pool.n_pages:
                raise ValueError(
                    f"request needs {worst} pages > pool total "
                    f"{self.kv.pool.n_pages}; even preemption cannot "
                    f"make it fit")
        if self.cfg.is_encoder_decoder:
            if not extras or "audio_embeds" not in extras:
                raise ValueError(
                    f"{self.cfg.name} is encoder-decoder: submit() needs "
                    f"extras={{'audio_embeds': (S_e, d_model)}} — encoded "
                    f"once at admission into the read-only shared "
                    f"encoder-KV plane (DESIGN.md §12)")
            ae = np.asarray(extras["audio_embeds"], np.float32)
            if ae.ndim == 2:
                ae = ae[None]
            if ae.shape[0] != 1 or ae.shape[1] != self.cfg.encoder_seq:
                raise ValueError(
                    f"audio_embeds must be (S_e={self.cfg.encoder_seq}, "
                    f"d_model) for one request; got {ae.shape}")
            extras = dict(extras, audio_embeds=ae)
        if temperature is not None and self._greedy:
            raise ValueError(
                "per-request temperature needs a stochastic sampler; this "
                "engine decodes greedily (argmax ignores temperature) — "
                "construct it with sampler=SamplerConfig(kind='categorical'"
                "/'topk'/'topp')")
        cap = (self.slot_len if self._spec_metrics is None
               else self._spec_cap)
        if not self._unbounded and prompt.size + max_new_tokens > cap:
            detail = (f"slot_len={self.slot_len}" if cap == self.slot_len
                      else f"the speculative ring cap {cap} (= min(slot_"
                           f"len, sliding_window); a wrapped ring cannot "
                           f"roll back rejected verify chunks)")
            raise ValueError(
                f"request needs {prompt.size + max_new_tokens} KV "
                f"positions > {detail}")
        req = GenRequest(prompt=prompt, max_new_tokens=max_new_tokens,
                         arrival=self.step_count, on_token=on_token,
                         on_finish=on_finish, temperature=temperature,
                         extras=extras, priority=priority,
                         deadline_ms=(deadline_ms if deadline_ms is not None
                                      else self._deadline_ms),
                         deadline_steps=deadline_steps,
                         submit_ns=time.perf_counter_ns())
        self.obs.req_submitted(req.rid, self.step_count)
        if not self.sched.submit(req):
            # bounded admission queue is full: reject with backpressure —
            # the request is terminal NOW, never retained (DESIGN.md §14)
            req.finish("rejected")
            self.obs.req_finished(req.rid, 0, "rejected")
            return req
        return req

    # ------------------------------------------------------------------
    def _sample_rows(self, logits, reqs: List[GenRequest]) -> np.ndarray:
        """logits (B, V) for exactly ``reqs`` rows -> (B,) int32."""
        if self.sampler.kind == "greedy":
            return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        temps = None
        if any(r.temperature is not None for r in reqs):
            temps = np.asarray(
                [self.sampler.temperature if r.temperature is None
                 else r.temperature for r in reqs], np.float32)
        self._rng, sub = jax.random.split(self._rng)
        return np.asarray(sample(sub, logits, self.sampler,
                                 temperature=temps))

    # ------------------------------------------------------------------
    # admission
    def _start_admissions(self) -> None:
        """Move policy-selected waiting requests into slots; their
        prompts prefill as chunks over the coming steps (or this step,
        when unchunked).  Paged mode additionally gates admission on the
        page pool: the policy's pick must be able to reserve its
        worst-case ``ceil((prompt+max_new)/page_size)`` pages, else
        admission stalls until releases free pages (head-of-line on
        memory — the no-preemption discipline, DESIGN.md §9).

        With a prefix cache, the pick's cached full-page prefix counts
        as admission credit (those pages are adopted, not allocated) and
        its prefill starts at the divergence point.  With preemption,
        preempted requests resume FIRST (head-of-line: fresh arrivals
        must not starve a swapped request), admission reserves only
        ``prompt+1`` tokens of pages, and a stalled pick may swap out a
        strictly lower-priority victim (DESIGN.md §13)."""
        while self.kv.n_free and (self.sched.has_waiting or self._swapped):
            if self.paged:
                if self._swapped:
                    sw = min(self._swapped,
                             key=lambda s: (-s.req.priority, s.seq))
                    pick = (self.sched.peek_next(self.usage)[1]
                            if self.sched.has_waiting else None)
                    if pick is None or sw.req.priority >= pick.priority:
                        if not self._try_resume():
                            break  # no new admissions past a stuck resume
                        continue
                    # else: the strictly higher-priority arrival admits
                    # first — resuming its own preemption victim here
                    # would hand back the pages _make_room just freed
                    # for it and ping-pong forever
                if not self.sched.has_waiting:
                    break
                idx, cand = self.sched.peek_next(self.usage)
                # per-arch admission cost (scheduler.admission_cost):
                # only growing kv planes claim pool positions — a pure-
                # recurrent stack reserves ZERO pages however long the
                # request runs, so its admission can never stall on the
                # pool (only on free slots)
                need = admission_cost(self.cfg, len(cand.prompt),
                                      cand.max_new_tokens).kv_positions
                base, shared = self._prefix_lookup(cand.prompt)
                # optimistic reservation under preemption: the prompt
                # plus one decode position; growth claims pages step by
                # step (_grow_running_rows) and preempts when dry
                reserve = (len(cand.prompt) + 1
                           if self._preempt and need else need)
                if not self.kv.can_admit(reserve, len(shared)):
                    if self._make_room(cand):
                        continue  # re-peek: eviction may drop cached pids
                    break
                req = self.sched.pop_at(idx)
                self.obs.req_admitted(req.rid, self.step_count - req.arrival)
                slot = self.kv.allocate(req.rid, reserve,
                                        shared_pages=shared, base=base)
                req.slot = slot
                self._note_prefix_hit(base)
                if self.cfg.is_encoder_decoder:
                    # admission-time encode: the shared encoder-KV plane
                    # is written once into the slot and only READ by
                    # every decode step after (never scattered to)
                    self.kv.write_enc_kv(
                        slot, self._exec.encode(req.extras["audio_embeds"]))
                # no accumulator state: chunks write the slot's pages
                self._admissions.append(Admission(
                    rid=req.rid, slot=slot, total=len(req.prompt),
                    next_lo=base, state=None, req=req))
                continue
            req = self.sched.pop_next(self.usage)
            self.obs.req_admitted(req.rid, self.step_count - req.arrival)
            slot = self.kv.allocate(req.rid)
            req.slot = slot
            state = self.kv.new_row_state()
            if self.cfg.is_encoder_decoder:
                # B=1 encode at admission; installed into the slot with
                # the rest of the row state by write_prefill
                state["enc_kv"] = self._exec.encode(
                    req.extras["audio_embeds"])
            self._admissions.append(Admission(
                rid=req.rid, slot=slot, total=len(req.prompt),
                state=state, req=req))

    # ------------------------------------------------------------------
    # prefix reuse + preemption (DESIGN.md §13)
    def _prefix_lookup(self, tokens):
        if self._prefix is None:
            return 0, []
        return self._prefix.lookup(np.asarray(tokens))

    def _note_prefix_hit(self, base: int) -> None:
        if not base:
            return
        self._prefills_skipped += 1
        self._prefix_hit_tokens += base

    def _prefix_insert(self, tokens, slot: int) -> None:
        """Index the slot's full prompt pages after its prefill finished
        (the pages are immutable from here on: all further writes land
        past the last full page ordinal).  Registered pages gain a cache
        reference BEFORE capacity evictions are released — the order
        matters when the insert itself overflows the capacity."""
        n_full = len(tokens) // self.kv.page_size
        if not n_full:
            return
        pids = self.kv.pool.owned[slot][:n_full]
        registered, evicted = self._prefix.insert(np.asarray(tokens), pids)
        for pid in registered:
            self.kv.pool.incref(pid)
        if evicted:
            self.kv.free_cached_pages(evicted)

    def _evict_prefix_pages(self) -> int:
        """Evict LRU prefix entries until DEVICE pages actually free (a
        node whose page other slots still adopt frees nothing); returns
        the number freed, 0 when the cache is exhausted."""
        if self._prefix is None:
            return 0
        while True:
            pids = self._prefix.evict_lru()
            if not pids:
                return 0
            freed = self.kv.free_cached_pages(pids)
            if freed:
                return len(freed)

    def _pick_victim(self, exclude_slot: Optional[int] = None,
                     max_priority: Optional[int] = None
                     ) -> Optional[GenRequest]:
        """Lowest-priority, latest-arrival running row (admitting rows
        excluded — a half-prefilled slot has nothing consistent to
        swap).  ``max_priority`` restricts to STRICTLY lower priorities:
        an admission/resume never preempts its own class (no ping-pong);
        decode growth passes no floor (it must proceed)."""
        admitting = {a.rid for a in self._admissions}
        cands = [r for r in self.sched.running
                 if r.rid not in admitting and r.slot != exclude_slot
                 and (max_priority is None or r.priority < max_priority)]
        if not cands:
            return None
        return min(cands, key=lambda r: (r.priority, -r.arrival, -r.rid))

    def _preempt_req(self, req: GenRequest) -> None:
        """Swap a running row out: stage its pages to the host pool
        (d2h) when the budget holds them, else drop them for recompute
        resume; either way the device pages free for the beneficiary."""
        slot = req.slot
        n_live = self.kv.length(slot)
        blob = self.kv.swap_out(slot)  # None => drop + recompute
        self.kv.release(slot)
        self.sched.preempt(req)
        self._swap_seq += 1
        self._swapped.append(_Swapped(
            req=req, blob=blob, next_tok=int(self.tokens[slot, 0]),
            n_tokens=n_live, seq=self._swap_seq))
        req.slot = None

    def _make_room(self, cand: Optional[GenRequest] = None) -> bool:
        """Free device pages for a stalled admission/resume: prefix-
        cache eviction first (cheapest — cached pages are speculative
        capital), then a strictly-lower-priority victim swap when
        preemption is on.  Returns True when pages were freed."""
        if self._evict_prefix_pages():
            return True
        if not self._preempt:
            return False
        victim = self._pick_victim(
            max_priority=cand.priority if cand is not None else None)
        if victim is None:
            return False
        self._preempt_req(victim)
        return True

    def _try_resume(self) -> bool:
        """Re-admit the best swapped request (priority, then preemption
        order).  Host-swapped pages scatter back verbatim (h2d) and the
        row decodes on; dropped KV re-prefills prompt+generated[:-1]
        through the normal admission machinery (with prefix credit) and
        feeds the pending token instead of sampling — bitwise either
        way under greedy decode."""
        sw = min(self._swapped, key=lambda s: (-s.req.priority, s.seq))
        req = sw.req
        if (sw.blob is not None and self.faults is not None
                and self.faults.fires("swap_in")):
            # h2d restage failed: drop the staged pages and fall to the
            # degrade rung below blob resume — recompute (DESIGN.md §14)
            self.kv.discard_blob(sw.blob)
            sw.blob = None
        if sw.blob is not None:
            while not self.kv.can_admit(sw.n_tokens + 1):
                if not self._make_room(req):
                    return False
            slot = self.kv.swap_in(req.rid, sw.blob, sw.n_tokens + 1)
            req.slot = slot
            self.sched.resume(req)
            self.tokens[slot, 0] = sw.next_tok
            self._swapped.remove(sw)
            return True
        ext = np.concatenate(
            [req.prompt, np.asarray(req.generated[:-1], np.int32)])
        n_live = len(ext)
        base, shared = self._prefix_lookup(ext)
        while not self.kv.can_admit(n_live + 1, len(shared)):
            if not self._make_room(req):
                return False
            base, shared = self._prefix_lookup(ext)  # eviction-safe redo
        slot = self.kv.allocate(req.rid, n_live + 1,
                                shared_pages=shared, base=base)
        req.slot = slot
        self.sched.resume(req)
        self._recomputes += 1
        self._note_prefix_hit(base)
        self._admissions.append(Admission(
            rid=req.rid, slot=slot, total=n_live, next_lo=base,
            state=None, req=req, tokens=ext, resume_tok=sw.next_tok))
        self._swapped.remove(sw)
        return True

    def _grow_running_rows(self) -> None:
        """Preemption mode: secure every running row's next decode
        position BEFORE the step plan forms — a mid-step preemption
        would tear rows the plan already scheduled.  Rows grow
        best-first (priority desc, arrival asc) so the rows not yet
        grown are exactly the preferred victims."""
        admitting = {a.rid for a in self._admissions}
        rows = sorted((r for r in self.sched.running
                       if r.rid not in admitting),
                      key=lambda r: (-r.priority, r.arrival, r.rid))
        for req in rows:
            if req.state != RUNNING:
                continue  # already taken as an earlier row's victim
            n = self.kv.length(req.slot) + 1
            while not self.kv.can_grow(req.slot, n):
                if self._evict_prefix_pages():
                    continue
                victim = self._pick_victim(exclude_slot=req.slot)
                if victim is None:
                    raise RuntimeError(
                        "page pool exhausted with nothing left to "
                        "preempt (submit() guards that a lone request "
                        "always fits the pool)")
                self._preempt_req(victim)
            self.kv.grow(req.slot, n)

    def _run_chunks(self, chunks) -> List[GenRequest]:
        """Execute this step's prefill chunks; complete admissions whose
        final chunk ran (sample the first token, then install the row).

        Budgeted mode defers the ``write_prefill`` install to the START
        of the next step (``_install_ready``): this step's batched
        decode runs over every slot, and a freshly-written row that is
        not in the planned decode rows would otherwise be silently
        advanced — KV written, pos bumped, token discarded — skipping
        one output token.  Unchunked mode installs immediately because
        the recomputed decode rows include the new row the same step
        (legacy admission timing)."""
        finished = []
        by_rid = {a.rid: a for a in self._admissions}
        for task in chunks:
            adm = by_rid[task.rid]
            req: GenRequest = adm.req
            t0 = (self.obs.clock_ns()
                  if self.obs.tracer is not None else 0)
            # recompute-resume admissions prefill prompt+generated[:-1]
            # instead of the prompt (DESIGN.md §13)
            src = adm.tokens if adm.tokens is not None else req.prompt
            tokens = jnp.asarray(src[None, task.lo: task.hi])
            if self.paged:
                # chunk writes straight into the slot's pool pages —
                # allocate up to the chunk's end, then adopt the state
                # (view(): chunks see the full, freshly-synced table)
                self.kv.ensure(adm.slot, task.hi)
                logits, new_state = self._exec.prefill_chunk_row(
                    self.kv.view(), tokens, adm.slot)
                self.kv.adopt(new_state)
                self.kv.note_tokens(adm.slot, task.hi)
            else:
                logits, adm.state, _ = self._exec.prefill_chunk(
                    adm.state, tokens)
            self.obs.req_chunk(req.rid, task.lo, task.hi, t0)
            adm.next_lo = task.hi
            if task.last:
                if self.paged and self._prefix is not None:
                    # the prefilled full pages are immutable from here on
                    # — index them BEFORE any release path below so the
                    # cache reference outlives the slot
                    self._prefix_insert(src, adm.slot)
                if adm.resume_tok is not None:
                    # recompute resume: the pending token was emitted
                    # before preemption — feed it, never re-sample it
                    self.tokens[adm.slot, 0] = int(adm.resume_tok)
                    self._admissions.remove(adm)
                    continue
                if self.faults is not None:
                    # a genuinely-poisoned prefill fails at its first
                    # sample, before the row ever joins the decode batch
                    row = np.asarray(logits[:, -1])
                    if not np.isfinite(row).all():
                        self._nan_quarantined += 1
                        self._admissions.remove(adm)
                        self._fail_row(req, "nan")
                        finished.append(req)
                        continue
                first = int(self._sample_rows(logits[:, -1], [req])[0])
                req.emit(first)
                if self._done(req, first):
                    self._admissions.remove(adm)
                    self.kv.release(adm.slot)
                    self.sched.evict(req, self._reason(req, first))
                    self.obs.req_finished(req.rid, len(req.generated),
                                          req.finish_reason)
                    finished.append(req)
                    continue
                self.obs.req_decode_start(req.rid)
                self.tokens[adm.slot, 0] = first
                if self.paged:
                    # KV is already in place — the row joins the decode
                    # rows as soon as the plan includes it (this step
                    # when unchunked, next step's plan under a budget:
                    # the same timing the dense install path produces)
                    self._admissions.remove(adm)
                elif self.budget is None:
                    self.kv.write_prefill(adm.state, adm.slot)
                    self._admissions.remove(adm)
                # else: adm.done marks it ready; installed next step
        return finished

    def _install_ready(self) -> None:
        """Install admissions whose final chunk ran last step (budgeted
        mode): scatter the finished B=1 state into the slot; the row
        enters this step's decode rows."""
        for adm in [a for a in self._admissions if a.done]:
            self.kv.write_prefill(adm.state, adm.slot)
            self._admissions.remove(adm)

    def _plan(self) -> StepPlan:
        """This step's mixed batch: every decodable row + prompt chunks
        under the token budget (unchunked mode: whole prompts this step,
        split only at the KV ring width, no budget)."""
        if self._preempt:
            # secure every running row's next decode position BEFORE the
            # plan forms — preempting a row the plan already scheduled
            # would tear the step (DESIGN.md §13)
            self._grow_running_rows()
        self._install_ready()
        self._start_admissions()
        decode_rows = self._decode_rows()
        if self.budget is not None:
            return self.budget.plan(decode_rows, self._admissions)
        plan = StepPlan(decode_rows=decode_rows)
        for adm in self._admissions:
            # whole prompt as one chunk; prompts longer than the ring
            # (unbounded SWA) split at slot_len so chunk writes never
            # overlap themselves
            for lo in range(adm.next_lo, adm.total, self.slot_len):
                hi = min(lo + self.slot_len, adm.total)
                plan.chunks.append(ChunkTask(rid=adm.rid, slot=adm.slot,
                                             lo=lo, hi=hi,
                                             last=hi >= adm.total))
        return plan

    def _decode_rows(self) -> List[int]:
        admitting = {a.rid for a in self._admissions}
        return sorted(r.slot for r in self.sched.running
                      if r.rid not in admitting)

    def _done(self, req: GenRequest, tok: int) -> bool:
        return (len(req.generated) >= req.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id))

    def _reason(self, req: GenRequest, tok: int) -> str:
        return ("eos" if self.eos_id is not None and tok == self.eos_id
                else "length")

    # ------------------------------------------------------------------
    # request-lifecycle hardening (DESIGN.md §14)
    def _fail_row(self, req: GenRequest, reason: str) -> None:
        """Terminal exit for a RUNNING row: release its slot (pages
        decref-then-free, draft ring unbound) before the scheduler sees
        the eviction — the release order every normal finish uses."""
        self.kv.release(req.slot)
        if self.spec_k > 0:
            self._draft_rid[req.slot] = -1
        self.sched.evict(req, reason)
        self.obs.req_finished(req.rid, len(req.generated), reason)

    def _terminate(self, rid: int, reason: str) -> bool:
        """Tear one in-flight request down wherever it currently lives —
        waiting queue, mid-prefill admission, running row, or swapped
        out — releasing paged KV, draft-ring binding and (recompute
        path) host-staged pages without leaking.  Prefix-cache refs the
        request's prompt REGISTERED survive by design: cached pages are
        the cache's capital, not the request's."""
        for req in self.sched.waiting:
            if req.rid == rid:
                self.sched.drop(req, reason)
                self.obs.req_finished(rid, len(req.generated), reason)
                return True
        # mid-prefill: the request is in sched.running WITH an admission
        # record — tear the admission first so the chunk plan forgets it
        for adm in self._admissions:
            if adm.rid == rid:
                self._admissions.remove(adm)
                self._fail_row(adm.req, reason)
                return True
        for req in self.sched.running:
            if req.rid == rid:
                self._fail_row(req, reason)
                return True
        for sw in self._swapped:
            if sw.req.rid == rid:
                self._swapped.remove(sw)
                if sw.blob is not None:
                    self.kv.discard_blob(sw.blob)
                self.sched.drop(sw.req, reason)
                self.obs.req_finished(rid, len(sw.req.generated), reason)
                return True
        return False

    def cancel(self, rid: int) -> bool:
        """Client abandonment (DESIGN.md §14): terminal status
        ``cancelled``, callable between steps.  Returns False when the
        rid is unknown or already terminal.  Every resource the request
        held — KV slot/pages, draft-ring row, host-swap blob — is
        released; the surviving requests' token streams are bitwise
        those of a run where this request never existed (greedy
        sampling; tests/test_faults.py)."""
        return self._terminate(rid, "cancelled")

    def _expire_deadlines(self) -> None:
        """Fail requests past their wall-clock (``deadline_ms``) or
        engine-step (``deadline_steps``) budget, wherever they live.
        Runs at the top of every step — a deadline can expire while the
        request is still queued, mid-prefill, decoding, or swapped."""
        cands = [r for r in (self.sched.waiting + self.sched.running
                             + [sw.req for sw in self._swapped])
                 if r.deadline_ms is not None or r.deadline_steps is not None]
        if not cands:
            return
        now = time.perf_counter_ns()
        for req in cands:
            over = (req.deadline_steps is not None
                    and self.step_count - req.arrival >= req.deadline_steps)
            if (not over and req.deadline_ms is not None
                    and req.submit_ns is not None):
                over = (now - req.submit_ns) > req.deadline_ms * 1e6
            if over:
                self._terminate(req.rid, "deadline")

    def _quarantine(self, last: np.ndarray,
                    reqs: List[GenRequest],
                    finished: List[GenRequest]) -> List[GenRequest]:
        """Poison injection + NaN/Inf row quarantine (DESIGN.md §14).
        ``last`` is the step's host-side (max_slots, V) last-position
        logits; an injected ``nan_logits`` fault poisons the lowest-rid
        decode row.  Poisoned rows fail (reason ``nan`` → status
        ``failed``) and release their state; survivors' logits are
        untouched, so their argmax stays bitwise the fault-free run."""
        if reqs and self.faults.fires("nan_logits"):
            victim = min(reqs, key=lambda r: r.rid)
            last[victim.slot, :] = np.nan
        finite = np.isfinite(last).all(axis=-1)
        bad = [r for r in reqs if not finite[r.slot]]
        for req in bad:
            self._nan_quarantined += 1
            self._fail_row(req, "nan")
            finished.append(req)
        if bad:
            reqs = [r for r in reqs if finite[r.slot]]
        return reqs

    def _audit_step(self) -> None:
        if self._check_inv:
            self.check_invariants()
        else:
            self.sched.check_invariants()

    def check_invariants(self) -> None:
        """Step-boundary accounting audit (DESIGN.md §14): scheduler
        state-list consistency, the KV manager's free/live partition and
        exact per-page refcounts (prefix-cache refs included), the draft
        ring's slot ledger, and host-pool occupancy == the pages staged
        by currently-swapped requests.  Cheap host-side bookkeeping only
        — never a device fetch — but O(pages), so it is opt-in
        (``check_invariants=True``) outside tests."""
        self.sched.check_invariants()
        cache_pages = self._prefix.pages() if self._prefix is not None else ()
        self.kv.check_invariants(cache_pages)
        if self._spec_metrics is not None:
            self._draft_kv.check_invariants()
        host = getattr(self.kv, "host", None)
        if host is not None:
            staged = sum(sw.blob["n_pages"] for sw in self._swapped
                         if sw.blob is not None)
            assert host.in_use == staged, \
                f"host pool holds {host.in_use} pages but swapped " \
                f"requests staged {staged}"

    # ------------------------------------------------------------------
    def step(self) -> List[GenRequest]:
        """One engine step: run the step plan (prefill chunks + one
        batched decode over the planned rows).  Returns requests
        finished this step."""
        if self.faults is not None and self.faults.fires("slow_step"):
            # injected stall: a slow step must trip wall-clock deadlines
            # (checked right below) exactly like a real device hiccup
            time.sleep(self.faults.stall_ms() / 1e3)
        self._expire_deadlines()
        # the step's phases: each span opens where its phase starts
        st = self.obs.step_begin(self.step_count)
        if st is not None:
            st.phase("plan")
        plan = self._plan()
        if st is not None:
            st.phase("chunk")
        finished = self._run_chunks(plan.chunks)
        # unchunked admission keeps the legacy timing: a request admitted
        # this step decodes this step.  Budgeted (chunked) steps decode
        # exactly the planned rows so the budget accounting stays exact.
        rows = (self._decode_rows() if self.budget is None
                else plan.decode_rows)
        if not rows:
            self.obs.step_end(st, n_chunks=len(plan.chunks))
            if plan.chunks:
                self.step_count += 1
                self._audit_step()
            return finished
        if st is not None:
            st.phase("dispatch")
        reqs = sorted((r for r in self.sched.running
                       if r.slot in set(rows)), key=lambda r: r.slot)
        if self._spec_metrics is not None:
            # one draft-and-verify round for the whole batch; k is
            # clipped so no row can emit past its budget (a row with one
            # token left falls the batch back to the plain step below —
            # which is what non-speculative decode would run anyway)
            k_round = min([self.spec_k]
                          + [r.max_new_tokens - len(r.generated) - 1
                             for r in reqs])
            if k_round >= 1:
                return self._step_speculative(st, plan, finished, rows,
                                              reqs, k_round)
        active = np.zeros((self.max_slots,), bool)
        active[rows] = True
        if self.paged:
            # page for each row's write position, then slice the table
            # to the live horizon: attention pays for live context, not
            # slot capacity (DESIGN.md §9)
            for r in rows:
                self.kv.ensure(r, self.kv.length(r) + 1)
            step_state = self.kv.view(self.kv.live_width(rows))
            act_dev = jnp.asarray(active)
        else:
            step_state = self.kv.state
            act_dev = None
        # fault mode decodes to host-side LOGITS on both planes so
        # poisoned rows can be quarantined before sampling; the plain
        # plane switches from the fused-argmax step to the gather
        # program — the oracle's own decode, so survivor logits (hence
        # their argmax) carry the very values the fused step reduces
        quar = self.faults is not None
        if self.offload is not None:
            # offloaded decode: layerwise packed step over the slotted
            # state; free slots bypass the expert pool (active mask), so
            # their dummy tokens never pollute the cache or the stats
            logits, state, self._pstate, route_ids = self._exec.decode(
                step_state, jnp.asarray(self.tokens), self._pstate,
                jnp.asarray(active))
            if self._collect:
                self._route_fetch(route_ids, rows)
            nxt_dev = (jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
                       if self._greedy and not quar else logits[:, -1])
        elif quar:
            out = self._exec.decode(
                step_state, jnp.asarray(self.tokens), active=act_dev,
                collect_info=self._collect)
            if self._collect:
                logits, state, _, (info_stack, _) = out
                ids, _ = routing_from_info(self.cfg, info_stack,
                                           want_hiddens=False)
                self.usage.update(ids, rows=rows)
            else:
                logits, state, _, _ = out
            nxt_dev = logits[:, -1]
        else:
            out = self._exec.decode_sampled(
                step_state, jnp.asarray(self.tokens),
                collect_info=self._collect, greedy=self._greedy,
                active=act_dev)
            if self._collect:
                nxt_dev, state, (info_stack, _) = out
                ids, _ = routing_from_info(self.cfg, info_stack,
                                           want_hiddens=False)
                self.usage.update(ids, rows=rows)
            else:
                nxt_dev, state = out
        if st is not None:
            st.phase("sync" if quar or self._greedy else "sample")
        if self.paged:
            self.kv.adopt(state)
            for r in rows:
                self.kv.note_tokens(r, self.kv.length(r) + 1)
        else:
            self.kv.state = state
        if quar:
            # (max_slots, V) host fetch: the price of inspecting logits
            # before sampling — paid only when an injector is attached
            # (copied: the poison write needs a writable buffer)
            last = np.asarray(nxt_dev).copy()
            if st is not None:
                st.phase("host" if self._greedy else "sample")
            reqs = self._quarantine(last, reqs, finished)
            nxt = np.zeros((self.max_slots,), np.int32)
            if reqs:
                srows = [r.slot for r in reqs]
                if self._greedy:
                    nxt[srows] = np.argmax(
                        last[srows], axis=-1).astype(np.int32)
                else:
                    nxt[srows] = self._sample_rows(
                        jnp.asarray(last[srows]), reqs)
        elif self._greedy:
            # the step's one blocking device fetch — everything the
            # device is still computing lands in this phase
            nxt = np.asarray(nxt_dev)
        else:
            nxt = self._sample_rows(
                jnp.asarray(nxt_dev)[np.asarray(rows)], reqs)
            full = np.zeros((self.max_slots,), np.int32)
            full[np.asarray(rows)] = nxt
            nxt = full
        if st is not None:
            st.phase("host")
        for req in reqs:
            t = int(nxt[req.slot])
            req.emit(t)
            if self._done(req, t):
                self.kv.release(req.slot)
                self.sched.evict(req, self._reason(req, t))
                self.obs.req_finished(req.rid, len(req.generated),
                                      req.finish_reason)
                finished.append(req)
            else:
                self.tokens[req.slot, 0] = t
        self.step_count += 1
        self._audit_step()
        if st is not None:
            self.obs.step_end(st, n_decode=len(reqs),
                              n_chunks=len(plan.chunks))
        return finished

    def _route_fetch(self, route_ids, rows) -> None:
        """Fetch the packed decode's route ids to the host (one blocking
        device→host copy per MoE layer) into the usage histogram, inside
        an ``engine.route_fetch`` span."""
        xo = self._xobs
        if xo is not None:
            xo.begin("engine.route_fetch")
        ids = [np.asarray(i) for i in route_ids]
        self.usage.update(ids, rows=rows)
        if xo is not None:
            xo.end(fetches=len(ids))

    # ------------------------------------------------------------------
    # token-level draft-and-verify decoding (DESIGN.md §11)
    def _draft_admit(self, req: GenRequest) -> None:
        """Bind a running request to its slot's draft-state row: B=1
        draft prefill over the prompt, scattered in at the draft ring
        width.  Lazy — runs at the first speculative step that touches
        the row, so requests admitted under any admission mode (whole,
        chunked, paged) pick up draft state identically."""
        slot = req.slot
        _, st, _ = self._draft_exec.prefill(
            jnp.asarray(req.prompt[None]), self._draft_kv.slot_len)
        self._draft_kv.write_prefill(st, slot)
        self._draft_consumed[slot] = req.prompt.size
        self._draft_rid[slot] = req.rid

    def _draft_propose(self, reqs: List[GenRequest], k: int) -> Dict[int, List[int]]:
        """Batched draft catch-up + proposal: every row first consumes
        its canonical tail (the tokens emitted since the draft last saw
        the stream), then proposes k greedy tokens, feeding itself the
        first k−1.  Rows run in lockstep (B = max_slots sub-steps); a
        row that finishes early dummy-feeds one position PAST its last
        real feed (dead under the validity mask, overwritten when a real
        token lands there).  The draft state's ``pos`` is host-
        authoritative: it is rebuilt from ``_draft_consumed`` before
        every sub-step, which is also what rolls rejected feeds back."""
        kvd = self._draft_kv
        queues: Dict[int, List[int]] = {}
        total: Dict[int, int] = {}
        fed: Dict[int, int] = {}
        props: Dict[int, List[int]] = {}
        for req in reqs:
            r = req.slot
            canon = np.concatenate(
                [req.prompt.astype(np.int64),
                 np.asarray(req.generated, np.int64)])
            q = [int(t) for t in canon[int(self._draft_consumed[r]):]]
            assert q, "draft ahead of the canonical stream"
            queues[r], total[r] = q, len(q) + k - 1
            fed[r], props[r] = 0, []
        state = kvd.state
        pos_dtype = state["pos"].dtype
        for _ in range(max(total.values())):
            toks = np.zeros((self.max_slots, 1), np.int32)
            pos = np.zeros((self.max_slots,), np.int64)
            for req in reqs:
                r = req.slot
                i = min(fed[r], total[r])  # done rows park one past last
                pos[r] = int(self._draft_consumed[r]) + i
                if fed[r] < len(queues[r]):
                    toks[r, 0] = queues[r][fed[r]]
                elif fed[r] < total[r]:
                    toks[r, 0] = props[r][fed[r] - len(queues[r])]
            state = dict(state, pos=jnp.asarray(pos).astype(pos_dtype))
            logits, state, _, _ = self._draft_exec.decode(
                state, jnp.asarray(toks))
            am = np.asarray(jnp.argmax(logits[:, -1], -1))
            for req in reqs:
                r = req.slot
                if fed[r] < total[r]:
                    fed[r] += 1
                    if fed[r] >= len(queues[r]):
                        props[r].append(int(am[r]))
        kvd.state = state  # pos is stale; _draft_consumed is the truth
        for req in reqs:
            self._draft_consumed[req.slot] += len(queues[req.slot])
        return props

    def _step_speculative(self, st, plan, finished, rows,
                          reqs: List[GenRequest], k_round: int
                          ) -> List[GenRequest]:
        """One draft-and-verify round over the running rows: draft
        proposes ``k_round`` tokens per row, the target verifies them in
        a single C = k_round+1 chunk through the executor, each row
        emits its longest matching prefix plus the target's own next
        token, and target KV (``truncate``) and draft bookkeeping roll
        back past each row's rejection point.  Bitwise the plain decode
        path under greedy sampling (tests/test_spec_decode.py)."""
        from repro.core.draft import verify_round
        C = k_round + 1
        for req in reqs:
            if self._draft_rid[req.slot] != req.rid:
                self._draft_admit(req)
        props = self._draft_propose(reqs, k_round)
        rec_snaps = {}
        if self._has_rec:
            # recurrent carries cannot roll back by a pos reset — the
            # verify chunk FOLDS rejected tokens into the fixed-size
            # state.  Mirror the paged page-table trim with the rec
            # plane's own trivial preemption primitive: snapshot each
            # row's pre-round state now, restore + replay the accepted
            # prefix after the verdict (DESIGN.md §12)
            for req in reqs:
                rec_snaps[req.slot] = self.kv.snapshot(req.slot)
        chunk = np.zeros((self.max_slots, C), np.int32)
        for req in reqs:
            chunk[req.slot, 0] = self.tokens[req.slot, 0]
            chunk[req.slot, 1:] = props[req.slot]
        active = np.zeros((self.max_slots,), bool)
        active[rows] = True
        base_len = {}
        if self.paged:
            for r in rows:
                base_len[r] = self.kv.length(r)
                self.kv.ensure(r, base_len[r] + C)
            step_state = self.kv.view(self.kv.live_width(rows))
            act_dev = jnp.asarray(active)
        else:
            step_state = self.kv.state
            act_dev = None
        if self.offload is not None:
            logits, state, self._pstate, route_ids = self._exec.decode(
                step_state, jnp.asarray(chunk), self._pstate,
                jnp.asarray(active))
            if self._collect:
                # packed route ids are token-major (B*C, K): map each
                # chunk position back to its slot for the usage histogram
                self._route_fetch(
                    route_ids, [r * C + j for r in rows for j in range(C)])
        else:
            logits, state, _, infos = self._exec.decode(
                step_state, jnp.asarray(chunk), active=act_dev,
                collect_info=self._collect)
            if self._collect:
                info_stack, _ = infos
                ids, _ = routing_from_info(self.cfg, info_stack,
                                           want_hiddens=False)
                tok_rows = [r * C + j for r in rows for j in range(C)]
                self.usage.update(ids, rows=tok_rows)
        if st is not None:
            st.phase("sync")
        if self.paged:
            self.kv.adopt(state)
            for r in rows:
                self.kv.note_tokens(r, base_len[r] + C)
        else:
            self.kv.state = state
        # the round's one blocking fetch: every row's target argmax
        tgt = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
        if st is not None:
            st.phase("host")
        for req in reqs:
            r = req.slot
            emitted, a = verify_round(props[r], tgt[r])
            self._spec_metrics.round(k_round, a)
            stopped = False
            for t in emitted:
                req.emit(int(t))
                if self._done(req, int(t)):
                    stopped = True
                    break
            if stopped:
                self.kv.release(r)
                self.sched.evict(req, self._reason(req, req.generated[-1]))
                self.obs.req_finished(req.rid, len(req.generated),
                                      req.finish_reason)
                finished.append(req)
                self._draft_rid[r] = -1
            else:
                # roll back to the canonical position: live KV is
                # prompt + generated minus the one un-fed last token —
                # exactly where non-speculative decode would stand
                self.tokens[r, 0] = req.generated[-1]
                if self._has_rec:
                    # restore the pre-round snapshot and replay the
                    # accepted feeds ONE TOKEN AT A TIME: C=1 steps are
                    # the plain engine's exact programs, so the restored
                    # carries (and any kv rings riding along) land
                    # bitwise where non-speculative decode would stand
                    # — a C-wide replay folds matmuls differently at
                    # the last partial chunk and drifts ~1e-7
                    snap = rec_snaps[r]
                    for j in range(len(emitted)):
                        _, snap, _, _ = self._exec.decode(
                            snap, jnp.asarray(chunk[r:r + 1, j:j + 1]))
                    self.kv.restore(snap, r)
                else:
                    self.kv.truncate(
                        r, len(req.prompt) + len(req.generated) - 1)
                self._draft_consumed[r] += min(a, k_round - 1)
        if self.offload is not None:
            hits, spec_hits, demand, spec_l = (
                int(c) for c in np.asarray(self._pstate.counts))
            total_h2d = (demand + spec_l) * self.offload.expert_bytes
            self._spec_metrics.add_bytes(total_h2d - self._spec_last_h2d)
            self._spec_last_h2d = total_h2d
        self.step_count += 1
        self._audit_step()
        if st is not None:
            self.obs.step_end(st, n_decode=len(reqs),
                              n_chunks=len(plan.chunks))
        return finished

    def run(self, max_steps: Optional[int] = None) -> List[GenRequest]:
        """Drive until every submitted request finishes; returns them in
        completion order."""
        steps = 0
        while (self.sched.has_waiting or self.sched.n_running
               or self._swapped):
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return self.sched.finished

    # ------------------------------------------------------------------
    # telemetry collectors (pull-time only — DESIGN.md §10)
    def _engine_metrics(self) -> Dict[str, float]:
        toks = sum(len(r.generated) for r in self.sched.finished)
        out = self.sched.metrics()
        out.update(steps=self.step_count, tokens=toks,
                   tokens_per_step=toks / max(1, self.step_count),
                   # every emitted token, still-running requests included
                   decode_tokens=toks + sum(len(r.generated)
                                            for r in self.sched.running))
        return out

    def _offload_metrics(self) -> Dict[str, float]:
        hits, spec_hits, demand, spec = (
            int(c) for c in np.asarray(self._pstate.counts))
        bytes_h2d = (demand + spec) * self.offload.expert_bytes
        # traffic counters cover every decode step, so normalize by
        # ALL emitted tokens — still-running requests included
        emitted = sum(len(r.generated)
                      for r in self.sched.finished + self.sched.running)
        return {"hits": hits, "spec_hits": spec_hits,
                "demand_loads": demand, "spec_loads": spec,
                "bytes_h2d": bytes_h2d,
                "bytes_per_token": bytes_h2d / max(1, emitted),
                "store_bytes": self.offload.store_bytes,
                "build_s": self.offload.build_s}

    def _prefix_metrics(self) -> Dict[str, float]:
        out = {"lookups": self._prefix.lookups,
               "hit_tokens": self._prefix_hit_tokens,
               "prefills_skipped": self._prefills_skipped}
        out.update(self._prefix.stats())
        return out

    def _kv_host_metrics(self) -> Dict[str, float]:
        out = dict(self.kv.host_stats())
        out.update(preemptions=self.sched.preemptions,
                   resumes=self.sched.resumes,
                   recomputes=self._recomputes,
                   swapped_now=len(self._swapped))
        return out

    def _faults_metrics(self) -> Dict[str, float]:
        """The ``faults`` namespace (DESIGN.md §14): injector fire
        counts (zeros without an injector), the executor's fetch
        retry/degrade ladder, NaN quarantines, and the terminal-status
        census over every request this engine has ever seen."""
        out = {"enabled": int(self.faults is not None), "injected": 0}
        for s in FAULT_SITES:
            out[f"fired_{s}"] = 0
        if self.faults is not None:
            out.update(self.faults.stats())
        out.update(self._exec.fault_counters)
        out["nan_quarantined"] = self._nan_quarantined
        counts = {"completed": 0, "cancelled": 0,
                  "deadline_exceeded": 0, "failed": 0}
        for r in self.sched.finished:
            counts[r.status] += 1
        counts["rejected"] = self.sched.queue_rejected
        out.update(counts)
        return out

    def metrics(self) -> Dict[str, Dict[str, object]]:
        """Namespaced telemetry snapshot ``{namespace: {key: value}}``
        (``repro.obs.schema``) — collectors pull fresh state at call
        time; timing namespaces appear when enabled."""
        return self.obs.snapshot()

    def stats(self) -> Dict[str, float]:
        """Legacy flat view — a pure projection of :meth:`metrics`
        through ``repro.obs.flatten_legacy`` (``engine.*`` flattens
        bare, ``kv.*`` → ``kv_*``, ``offload.*`` → ``offload_*``), so
        the two surfaces can never disagree on a value."""
        return self.obs.legacy_flat()
