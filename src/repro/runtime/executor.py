"""The unified block-execution core every engine dispatches through
(DESIGN.md §8).

One :class:`Executor` owns the per-layer-kind jitted block programs for a
model and runs them in three interchangeable **planes**:

* ``plain``             — dense resident weights, per-token expert gather
  (``moe_apply_gather``) through the scanned ``transformer.decode_step``;
* ``packed_vectorized`` — HQQ-packed experts served from the device
  buffer pool with the vectorized slot plans, staging synchronous inside
  the block program (DESIGN.md §6/§7);
* ``packed_pipelined``  — same data plane, but each MoE block splits into
  mixer / MoE / staging dispatches so speculative host→device copies
  overlap the next block's compute (DESIGN.md §7).

Every step is a **chunk**: decode is the C = 1 case and a prefill chunk
is the C > 1 case of the same block program (``decode_step`` /
``decode_block_packed*`` — the KV caches are written at positions
``pos .. pos+C−1``).  Whole-prompt prefill is therefore *chunked prefill
with one chunk*, which is what makes chunked ≡ whole bitwise: chunk size
only changes the number of query rows per dispatch, and every reduction
(softmax over the KV width, per-row matmuls) keeps its shape
(tests/test_runtime.py asserts bitwise equality on all planes).

Packed-plane prefill chunks stream their routed experts straight from
the host store (one ``pe_gather`` batch plan per layer per chunk,
``moe_apply_packed_stream``) and leave the LRU pool, staging tiers and
transfer counters untouched — prefill is the encode phase the paper's
cache does not manage, so chunking adds zero pool traffic.

All programs go through ``transformer.cached_jit`` under config-keyed
names, so every engine and every Executor instance of the same
(cfg, plane, mode) shares one compiled program per process
(``cached_jit_stats`` asserts this in the tests).  Each packed-plane
program is a named function (``embed``, ``head``, ``packed_mixer``,
``packed_moe``, ``packed_stage``, ``packed_blk``, ``packed_blk_plain``,
``packed_chunk_moe``, ``packed_chunk_moe_ids``), so its XLA module reads
``jit_<name>`` in a device trace.

The packed planes' layer programs (``packed_mixer``, ``packed_blk``,
``packed_blk_plain``) take the decode state of the layer's pattern
position whole — the layer-stacked tree — with the layer's period index
as a traced scalar: they read their layer out of it and write it back
inside the program, and donate the tree, so XLA updates the stack in
place and one program serves every layer of a kind.  Tail layers (past
the last whole period) hold unstacked state and pass it as is.  What
they donate is the step's own copy of the layer trees, made on the
device once a step, so a step never consumes its caller's state.

With an observer attached (:meth:`Executor.set_observer`,
``repro.obs.ExecSpans``) every dispatch site of the packed planes sits
in its own span: the jitted call with its argument preparation
(``exec.embed`` / ``exec.mixer`` / ``exec.moe`` / ``exec.stage`` /
``exec.block`` / ``exec.chunk_moe`` / ``exec.head``) and the step's copy
of the layer trees (``exec.state``).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, OffloadSpec, parse_block
from repro.core import expert_pool as EP
from repro.core import speculative
from repro.core.trace import stacked_routers
from repro.models import moe as M
from repro.models import transformer as T

PLANES = ("plain", "packed_vectorized", "packed_pipelined")


class Executor:
    """Unified step-plan executor (module docstring; DESIGN.md §8).

    ``spec``/``store`` are required for the packed planes (the offload
    configuration and the packed host store from
    ``quantize_for_offload(..., pack_experts=True)``).  ``fused`` /
    ``vectorized`` select the packed data plane (fused dequant-matmul
    kernels; batched vs PR-2 sequential slot swaps) — kept for the
    offload benchmark's measured baselines.
    """

    def __init__(self, params, cfg: ModelConfig, *, plane: str = "plain",
                 spec: Optional[OffloadSpec] = None, store=None,
                 fused: bool = True, vectorized: bool = True):
        if plane not in PLANES:
            raise ValueError(f"unknown plane {plane!r}; one of {PLANES}")
        self.plane = plane
        self.packed = plane != "plain"
        self.pipelined = plane == "packed_pipelined"
        self.params = params
        self.cfg = cfg
        self.spec = spec
        self.store = store
        self.fused = fused
        self.vectorized = vectorized
        # optional dispatch-site observer (repro.obs.ExecSpans);
        # host-side spans around dispatches only — never a device
        # sync.  Executors can be shared across engines (the offload
        # engine hands its decoder to ContinuousEngine), so the LAST
        # attached observer wins.
        self._obs = None
        # fault-injection plane (DESIGN.md §14): consulted host-side at
        # the expert-fetch boundary of the per-layer decode loop — jit
        # programs never see it.  Present on every plane (the plain
        # plane simply has no fetch site) so engines can attach/detach
        # unconditionally, mirroring the observer protocol.
        self._finj = None
        self._fetch_retries = 2
        self._fetch_backoff_ms = 0.0
        self.fault_counters = {"fetch_retries": 0, "fetch_degraded": 0}
        if self.packed:
            if spec is None or store is None:
                raise ValueError("packed planes need spec= and store= "
                                 "(see quantize_for_offload)")
            self.routers = jnp.asarray(stacked_routers(params, cfg))
            self.n_moe_layers = int(self.routers.shape[0])
            self.kinds = cfg.layer_kinds()
            # MoE ordinal of each absolute layer (period-major — the
            # order stacked_routers / the store use)
            self.moe_ordinal: Dict[int, int] = {}
            for l, k in enumerate(self.kinds):
                if parse_block(k)[1] == "moe":
                    self.moe_ordinal[l] = len(self.moe_ordinal)
            self._layer_p = [T.layer_params(params, cfg, l)
                             for l in range(cfg.n_layers)]
            # where each layer's decode state lives: ("stack", pattern
            # position, period index as a device int32 the layer
            # programs take traced) or ("tail", tail index, None)
            period = cfg.pattern_period
            n_scanned = cfg.n_periods * period
            self._state_at = [
                ("stack", l % period, jnp.asarray(l // period, jnp.int32))
                if l < n_scanned else ("tail", l - n_scanned, None)
                for l in range(cfg.n_layers)]
            def embed(p, t):
                return T.embed_tokens(p, cfg, t)

            def head(p, x):
                return T.apply_head(p, cfg, x)
            self._jit_embed = T.cached_jit(("embed", cfg),
                                           lambda: jax.jit(embed))
            self._jit_head = T.cached_jit(("head", cfg),
                                          lambda: jax.jit(head))
            # mode key: packed-block executables are shared across
            # executor instances with identical config+flags
            self._mode = (cfg, spec, fused, self.pipelined, vectorized)
            self._blk: Dict[Any, Any] = {}

    # ------------------------------------------------------------------
    def set_observer(self, obs) -> None:
        """Attach (or detach with ``None``) the dispatch-site observer
        for :meth:`decode` / :meth:`decode_sampled` and the packed
        prefill chunks — an object with ``begin(name, layer)`` /
        ``end(programs)`` (``repro.obs.ExecSpans``) whose sites match
        this plane's ``repro.obs.schema.EXEC_KEYS_BY_PLANE`` entry."""
        self._obs = obs

    def set_fault_injector(self, inj, *, max_retries: int = 2,
                           backoff_ms: float = 0.0) -> None:
        """Attach (or clear with ``None``) the seeded fault plane
        (DESIGN.md §14).  Site here: ``expert_fetch``
        (``core.expert_pool.FAULT_SITE``) — a fired fault means the
        pool-path h2d gather for one MoE layer failed; the decode loop
        retries up to ``max_retries`` times (sleeping ``backoff_ms``
        between attempts) and then degrades that layer to store-direct
        streaming, dropping speculative staging for the step.  Executors
        are shared across engines, so — like the observer — the LAST
        attached injector wins."""
        self._finj = inj
        self._fetch_retries = int(max_retries)
        self._fetch_backoff_ms = float(backoff_ms)
        # shared-executor semantics: each engine attaches on construction,
        # so the ladder counters always describe the CURRENT engine's run
        self.fault_counters = {"fetch_retries": 0, "fetch_degraded": 0}

    def _fetch_faulted(self) -> bool:
        """One MoE layer's fetch boundary: did the (retried) h2d fetch
        ultimately fail?  True = degrade this layer."""
        inj = self._finj
        if inj is None or not inj.fires(EP.FAULT_SITE):
            return False
        for _ in range(self._fetch_retries):
            self.fault_counters["fetch_retries"] += 1
            if self._fetch_backoff_ms > 0.0:
                time.sleep(self._fetch_backoff_ms / 1e3)
            if not inj.fires(EP.FAULT_SITE):
                return False  # a retry went through
        self.fault_counters["fetch_degraded"] += 1
        return True

    # ------------------------------------------------------------------
    # state / pool construction
    def init_state(self, batch: int, max_len: int):
        """Fresh decode state (stacked layout, scalar pos 0)."""
        return T.init_decode_state(self.cfg, batch, max_len)

    def encode(self, audio_embeds):
        """Encoder pass + cross-attn K/V collection — the admission-time
        computation of the read-only shared encoder-KV plane
        (DESIGN.md §12).  Returns the ``state["enc_kv"]`` pytree
        ({"k", "v": (n_layers, B, S_e, H_kv, Dh), "pos"}); runs once per
        request, referenced by every decode step, never scattered to."""
        assert self.cfg.is_encoder_decoder, "encode() is the enc-dec frontend"
        cfg = self.cfg
        fn = T.cached_jit(
            ("encode_enc_kv", cfg),
            lambda: jax.jit(lambda p, a: T.encode_enc_kv(p, cfg, a)))
        return fn(self.params, jnp.asarray(audio_embeds))

    def init_pool_state(self) -> "EP.PoolState":
        assert self.packed, "buffer pools exist on packed planes only"
        return EP.init_pool_state(self.store, self.spec)

    # ------------------------------------------------------------------
    # plain-plane programs (shared cache keys predate the runtime
    # refactor — every engine keeps reusing the same executables)
    def _plain_step(self, collect_info: bool):
        key = ("decode_gather_info" if collect_info else "decode_gather",
               self.cfg)
        cfg = self.cfg
        if collect_info:
            make = lambda: jax.jit(lambda p, st, tk, act: T.decode_step(
                p, cfg, st, tk, moe_mode="gather", collect_info=True,
                active=act))
        else:
            make = lambda: jax.jit(lambda p, st, tk, act: T.decode_step(
                p, cfg, st, tk, moe_mode="gather", active=act))
        return T.cached_jit(key, make)

    def _plain_step_sampled(self, collect_info: bool, greedy: bool):
        cfg, collect = self.cfg, collect_info

        def make():
            if collect:
                def _step_fn(p, st, tk, act):
                    logits, st, infos = T.decode_step(
                        p, cfg, st, tk, moe_mode="gather",
                        collect_info=True, active=act)
                    nxt = (jnp.argmax(logits[:, -1], -1)
                           .astype(jnp.int32) if greedy
                           else logits[:, -1])
                    return nxt, st, infos
            else:
                def _step_fn(p, st, tk, act):
                    logits, st = T.decode_step(p, cfg, st, tk,
                                               moe_mode="gather",
                                               active=act)
                    nxt = (jnp.argmax(logits[:, -1], -1)
                           .astype(jnp.int32) if greedy
                           else logits[:, -1])
                    return nxt, st
            return jax.jit(_step_fn, donate_argnums=1)
        return T.cached_jit(("cont_step", cfg, collect, greedy), make)

    def _row_chunk_step(self):
        """B=1 prefill chunk of one slot against the shared page pools
        (paged admission, DESIGN.md §9): ``decode_step(row=slot)`` —
        the chunk's KV lands in the pages the slot owns, no install.
        The state is donated (callers hand in a fresh view and adopt
        the result) so the pool scatters run in place instead of
        copying pool-capacity bytes per chunk."""
        cfg = self.cfg
        return T.cached_jit(
            ("decode_gather_row", cfg),
            lambda: jax.jit(lambda p, st, tk, r: T.decode_step(
                p, cfg, st, tk, moe_mode="gather", row=r),
                donate_argnums=1))

    # ------------------------------------------------------------------
    # packed-plane per-kind block programs.  Those that touch the decode
    # state take ``(stack, per)`` — the layer's stacked state tree and
    # its traced period index, or a tail layer's own state and None —
    # and return the tree with the layer written back; the tree is
    # donated, so it is always the step's own copy (_own_state)
    def _decode_blk(self, kind: str):
        if kind not in self._blk:
            # locals only in the closures: a `self` capture would pin the
            # whole executor (params + store) in the process-wide cache
            cfg, spec = self.cfg, self.spec
            fused, vectorized = self.fused, self.vectorized
            if parse_block(kind)[1] == "moe":
                def make():
                    def packed_blk(p, x, stack, per, pos, store, ps, lm,
                                   routers, act, pages):
                        x, st, ps, info = T.decode_block_packed(
                            p, cfg, kind, x, T.stack_layer(stack, per),
                            pos, store, ps, lm, routers,
                            lookahead=spec.lookahead,
                            n_spec=spec.num_speculative, fused=fused,
                            active=act, vectorized=vectorized, pages=pages)
                        return (x, T.stack_set_layer(stack, per, st), ps,
                                info)
                    return jax.jit(packed_blk, donate_argnums=(2, 6))
                key = ("packed_blk", self._mode, kind)
            else:
                def make():
                    def packed_blk_plain(p, x, stack, per, pos, pages,
                                         act):
                        x, st, info = T._block_decode(
                            p, cfg, kind, x, T.stack_layer(stack, per),
                            pos, moe_mode="gather", pages=pages,
                            active=act)
                        return x, T.stack_set_layer(stack, per, st), info
                    return jax.jit(packed_blk_plain, donate_argnums=(2,))
                # a non-MoE block's program depends only on (cfg, kind) —
                # identical across offload modes
                key = ("packed_blk_plain", cfg, kind)
            self._blk[kind] = T.cached_jit(key, make)
        return self._blk[kind]

    def _mixer_blk(self, kind: str):
        key = ("mixer", kind)
        if key not in self._blk:
            cfg = self.cfg

            def packed_mixer(p, x, stack, per, pos, pages, act):
                x, st, h2 = T.decode_block_packed_mixer(
                    p, cfg, kind, x, T.stack_layer(stack, per), pos,
                    pages=pages, active=act)
                return x, T.stack_set_layer(stack, per, st), h2
            self._blk[key] = T.cached_jit(
                ("packed_mixer", cfg, kind),
                lambda: jax.jit(packed_mixer, donate_argnums=(2,)))
        return self._blk[key]

    def _moe_blk(self):
        if "moe_ffn" not in self._blk:
            cfg = self.cfg
            fused, vectorized = self.fused, self.vectorized

            def make():
                def packed_moe(p, x, h2, store, ps, lm, act):
                    return T.decode_block_packed_moe(
                        p, cfg, x, h2, store, ps, lm, fused=fused,
                        vectorized=vectorized, active=act)
                return jax.jit(packed_moe, donate_argnums=(4,))
            self._blk["moe_ffn"] = T.cached_jit(("packed_moe", self._mode),
                                                make)
        return self._blk["moe_ffn"]

    def _stage_blk(self):
        if "stage" not in self._blk:
            n_spec = self.spec.num_speculative
            vectorized = self.vectorized

            def make():
                def packed_stage(store, ps, tgt, hidden, routers):
                    pred = speculative.predict_experts(
                        routers[tgt], hidden, n_spec)[0]
                    return EP.stage(store, ps, tgt, pred, True,
                                    vectorized=vectorized)
                return jax.jit(packed_stage, donate_argnums=(1,))
            self._blk["stage"] = T.cached_jit(("packed_stage", self._mode),
                                              make)
        return self._blk["stage"]

    def _chunk_moe_blk(self):
        """Prefill-chunk MoE: route + store-gather + packed compute — no
        pool state in the program at all (DESIGN.md §8)."""
        if "chunk_moe" not in self._blk:
            cfg, fused = self.cfg, self.fused

            def make():
                def packed_chunk_moe(p, x, h2, store, lm):
                    B, C, D = h2.shape
                    y2d, _ = M.moe_apply_packed_stream(
                        p["moe"], cfg, h2.reshape(B * C, D), store, lm,
                        fused=fused)
                    return x + y2d.reshape(B, C, D)
                return jax.jit(packed_chunk_moe)
            self._blk["chunk_moe"] = T.cached_jit(
                ("packed_chunk_moe", cfg, fused), make)
        return self._blk["chunk_moe"]

    def _chunk_moe_ids_blk(self):
        """Store-direct MoE that also returns the routed expert ids —
        the degraded decode path (DESIGN.md §14): same
        ``moe_apply_packed_stream`` -> ``_packed_compute`` pipeline as
        the pool path, so its activations are bitwise the pool path's;
        only the LRU/transfer counters differ (no pool traffic)."""
        if "chunk_moe_ids" not in self._blk:
            cfg, fused = self.cfg, self.fused

            def make():
                def packed_chunk_moe_ids(p, x, h2, store, lm):
                    B, C, D = h2.shape
                    y2d, info = M.moe_apply_packed_stream(
                        p["moe"], cfg, h2.reshape(B * C, D), store, lm,
                        fused=fused)
                    return x + y2d.reshape(B, C, D), info["ids"]
                return jax.jit(packed_chunk_moe_ids)
            self._blk["chunk_moe_ids"] = T.cached_jit(
                ("packed_chunk_moe_ids", cfg, fused), make)
        return self._blk["chunk_moe_ids"]

    # ------------------------------------------------------------------
    # packed-plane sites shared by decode and prefill chunks, each in
    # its span when an observer is attached
    def _embed(self, tokens):
        obs = self._obs
        if obs is not None:
            obs.begin("exec.embed")
        x = self._jit_embed(self.params, tokens)
        if obs is not None:
            obs.end(1)
        return x

    def _head(self, x):
        obs = self._obs
        if obs is not None:
            obs.begin("exec.head")
        logits = self._jit_head(self.params, x)
        if obs is not None:
            obs.end(1)
        return logits

    def _own_state(self, state):
        """The step's own copy of ``state``'s layer trees (``stack``,
        ``tail``), made on the device in one call: the layer programs
        donate it and update it in place, and the caller's state stays
        valid."""
        obs = self._obs
        if obs is not None:
            obs.begin("exec.state")
        held = jax.device_put({"stack": state["stack"],
                               "tail": state["tail"]}, may_alias=False)
        if obs is not None:
            obs.end()
        return held

    # ------------------------------------------------------------------
    def decode(self, state, tokens, pstate=None, active=None, *,
               collect_info: bool = False):
        """One decode step for every row — the unified engine entry.

        tokens: (B, C) int32 — C = 1 for plain decode, C = k+1 for a
        speculative verify chunk (DESIGN.md §11); KV is written at
        ``pos .. pos+C−1`` and ``pos`` advances by C (active rows).
        Returns ``(logits, state', pstate',
        info)`` on every plane; ``pstate`` threads the expert buffer pool
        (packed planes; ``None`` on plain), ``active`` (B,) bool masks
        rows whose output is discarded (continuous batching free slots).
        ``info`` is the per-MoE-layer route-id list on packed planes, the
        raw ``decode_step`` info stack when ``collect_info`` on plain,
        else ``None``.

        On paged-KV states (``"pages"`` in state) ``active`` also gates
        KV writes and per-row ``pos`` advance (DESIGN.md §9): frozen
        rows are idle slots or chunked admissions mid-fill.

        ``state`` stays valid: packed planes update a copy of it in
        place (:meth:`_own_state`).
        """
        obs = self._obs
        if not self.packed:
            if obs is not None:
                obs.begin("exec.dispatch")
            if collect_info:
                logits, state, infos = self._plain_step(True)(
                    self.params, state, tokens, active)
            else:
                logits, state = self._plain_step(False)(
                    self.params, state, tokens, active)
                infos = None
            if obs is not None:
                obs.end(1)
            return logits, state, None, infos
        x = self._embed(tokens)
        pos = state["pos"]
        pages = state.get("pages")
        B = int(tokens.shape[0])
        # speculation is the paper's batch-1 interactive feature (batched
        # continuous decode disables it) — same gate the synchronous
        # block applies inside jit via moe_apply_packed's T == 1 check
        speculate = (self.pipelined and self.spec.num_speculative > 0
                     and B * int(tokens.shape[1]) == 1)
        # a whole block (or a non-MoE layer) is the pipelined plane's
        # mixer site and the vectorized plane's block site
        blk_site = "exec.mixer" if self.pipelined else "exec.block"
        moe_site = "exec.moe" if self.pipelined else "exec.block"
        route_ids = []
        held = self._own_state(state)
        for l, kind in enumerate(self.kinds):
            where, i, per = self._state_at[l]
            st = held[where][i]
            lp = self._layer_p[l]
            if l not in self.moe_ordinal:
                if obs is not None:
                    obs.begin(blk_site, l)
                x, st, _ = self._decode_blk(kind)(
                    lp, x, st, per, pos, pages, active)
                if obs is not None:
                    obs.end(1)
            elif self._fetch_faulted():
                # retry ladder exhausted (DESIGN.md §14): degrade this
                # layer to store-direct streaming — bitwise the pool
                # path's activations (shared _packed_compute), zero pool
                # traffic, no speculative staging
                if obs is not None:
                    obs.begin(blk_site, l)
                x, st, h2 = self._mixer_blk(kind)(
                    lp, x, st, per, pos, pages, active)
                if obs is not None:
                    obs.end(1)
                    obs.begin(moe_site, l)
                lm = jnp.asarray(self.moe_ordinal[l], jnp.int32)
                x, ids = self._chunk_moe_ids_blk()(lp, x, h2, self.store, lm)
                if obs is not None:
                    obs.end(1)
                route_ids.append(ids)
            elif self.pipelined:
                if obs is not None:
                    obs.begin("exec.mixer", l)
                x, st, h2 = self._mixer_blk(kind)(
                    lp, x, st, per, pos, pages, active)
                if obs is not None:
                    obs.end(1)
                    obs.begin("exec.moe", l)
                lm = jnp.asarray(self.moe_ordinal[l], jnp.int32)
                x, pstate, info = self._moe_blk()(
                    lp, x, h2, self.store, pstate, lm, active)
                if obs is not None:
                    obs.end(1)
                tgt = self.moe_ordinal[l] + self.spec.lookahead
                if speculate and tgt < self.n_moe_layers:
                    if obs is not None:
                        obs.begin("exec.stage", l)
                    pstate = self._stage_blk()(
                        self.store, pstate, jnp.asarray(tgt, jnp.int32),
                        info["hidden_pre_moe"], self.routers)
                    if obs is not None:
                        obs.end(1)
                route_ids.append(info["route"]["ids"])
            else:
                if obs is not None:
                    obs.begin("exec.block", l)
                lm = jnp.asarray(self.moe_ordinal[l], jnp.int32)
                x, st, pstate, info = self._decode_blk(kind)(
                    lp, x, st, per, pos, self.store, pstate, lm,
                    self.routers, active, pages)
                if obs is not None:
                    obs.end(1)
                route_ids.append(info["route"]["ids"])
            held[where][i] = st
        logits = self._head(x)
        # decode is the C=1 case of a chunk; a C=k+1 verify chunk
        # (speculative decoding, DESIGN.md §11) advances by its width
        C = int(tokens.shape[1])
        if pages is not None and active is not None:
            pos = pos + jnp.where(active, C, 0).astype(pos.dtype)
        else:
            pos = pos + C
        state = dict(state, **held, pos=pos)
        return logits, state, pstate, route_ids

    def decode_sampled(self, state, tokens, *, collect_info: bool,
                       greedy: bool, active=None):
        """Plain-plane decode with sampling prep fused into the jitted
        step (greedy argmax on-device / last-position logits) and the
        state donated — the continuous engine's hot loop."""
        assert not self.packed, "packed decode returns logits; sample host-side"
        obs = self._obs
        if obs is not None:
            obs.begin("exec.dispatch")
        out = self._plain_step_sampled(collect_info, greedy)(
            self.params, state, tokens, active)
        if obs is not None:
            obs.end(1)
        return out

    # ------------------------------------------------------------------
    def _chunk_layers(self, state, x, pos, pages):
        """A prompt chunk's per-layer loop on the packed planes: mixer +
        store-streamed chunk MoE per MoE layer (no pool state), the
        whole block otherwise.  Returns (x, state)."""
        obs = self._obs
        blk_site = "exec.mixer" if self.pipelined else "exec.block"
        held = self._own_state(state)
        for l, kind in enumerate(self.kinds):
            where, i, per = self._state_at[l]
            st = held[where][i]
            lp = self._layer_p[l]
            if l in self.moe_ordinal:
                if obs is not None:
                    obs.begin("exec.mixer", l)
                x, st, h2 = self._mixer_blk(kind)(
                    lp, x, st, per, pos, pages, None)
                if obs is not None:
                    obs.end(1)
                    obs.begin("exec.chunk_moe", l)
                lm = jnp.asarray(self.moe_ordinal[l], jnp.int32)
                x = self._chunk_moe_blk()(lp, x, h2, self.store, lm)
            else:
                if obs is not None:
                    obs.begin(blk_site, l)
                x, st, _ = self._decode_blk(kind)(
                    lp, x, st, per, pos, pages, None)
            if obs is not None:
                obs.end(1)
            held[where][i] = st
        return x, dict(state, **held)

    def prefill_chunk(self, state, tokens, pstate=None):
        """Process prompt chunk ``tokens`` (B, C) at the rows' current
        positions: KV written at ``pos .. pos+C−1``, ``pos`` advances by
        C.  Returns ``(logits (B, C, V), state', pstate')`` — chunk MoE
        never touches the pool state (module docstring)."""
        if not self.packed:
            logits, state = self._plain_step(False)(
                self.params, state, tokens, None)
            return logits, state, pstate
        x = self._embed(tokens)
        pos = state["pos"]
        x, state = self._chunk_layers(state, x, pos, state.get("pages"))
        logits = self._head(x)
        state = dict(state, pos=pos + tokens.shape[1])
        return logits, state, pstate

    def prefill_chunk_row(self, state, tokens, slot: int):
        """One slot's prompt chunk against the shared page pools (paged
        admission, DESIGN.md §9): tokens (1, C) write KV straight into
        the pages ``slot`` owns at its current position and only that
        row's ``pos`` advances.  Returns (logits (1, C, V), state').
        There is no install step — the running batch reads the same
        pools the chunk just wrote."""
        assert "pages" in state, "prefill_chunk_row needs a paged-KV state"
        slot_t = jnp.asarray(slot, jnp.int32)
        if not self.packed:
            return self._row_chunk_step()(self.params, state, tokens, slot_t)
        x = self._embed(tokens)
        pos_row = jax.lax.dynamic_slice(state["pos"], (slot_t,), (1,))
        pages_row = jax.lax.dynamic_slice(
            state["pages"], (slot_t, 0), (1, state["pages"].shape[1]))
        x, state = self._chunk_layers(state, x, pos_row, pages_row)
        logits = self._head(x)
        state = dict(state, pos=jax.lax.dynamic_update_slice(
            state["pos"], pos_row + tokens.shape[1], (slot_t,)))
        return logits, state

    def prefill(self, tokens, max_len: int, *, chunk: Optional[int] = None,
                pstate=None, extras=None):
        """Whole-prompt prefill = chunked prefill over a fresh state —
        for EVERY layer kind in the config zoo (DESIGN.md §12).

        tokens: (B, S) int32, no padding (rows prefill alone or in
        equal-length lock-step; the static engine's left-padded batches
        go through :meth:`prefill_padded`).  ``chunk=None`` processes the
        prompt as ONE chunk; any chunking is bitwise-identical
        (tests/test_runtime.py): attention mixers only change the number
        of query rows per dispatch, recurrent mixers fold chunks through
        their sequential chunk forms whose carry composition is exact
        (``repro.models.recurrent.*_chunk``).  The full-sequence
        ``forward_train`` prefill (chunkwise-parallel train forms) stays
        available via :meth:`prefill_padded` — it matches this path only
        to recurrent-vs-chunkwise tolerance, never bitwise, which is why
        every serving engine and its oracle run THIS path.

        Encoder-decoder stacks need ``extras={"audio_embeds": (B, S_e,
        D)}``; the encoder runs once up front (:meth:`encode`) and the
        chunks read the resulting shared ``enc_kv`` plane.

        Returns (logits of the last chunk, state, pstate).
        """
        tokens = jnp.asarray(tokens)
        B, S = tokens.shape
        C = S if chunk is None else max(1, min(int(chunk), S))
        state = self.init_state(B, max_len)
        if self.cfg.is_encoder_decoder:
            if not extras or "audio_embeds" not in extras:
                raise ValueError(
                    f"{self.cfg.name} is encoder-decoder: prefill needs "
                    "extras={'audio_embeds': (B, encoder_seq, d_model)}")
            state["enc_kv"] = self.encode(extras["audio_embeds"])
        logits = None
        for lo in range(0, S, C):
            logits, state, pstate = self.prefill_chunk(
                state, tokens[:, lo: lo + C], pstate)
        return logits, state, pstate

    def prefill_padded(self, batch, max_len: int):
        """Left-padded batched prefill (static ``ServeEngine`` shape):
        the full-sequence ``forward_train`` pass with pad-mask isolation
        — a *different* program from the chunk path (dispatch MoE, S×S
        attention), kept for throughput-oriented static batches where
        all rows prefill together."""
        assert not self.packed, "packed engines prefill through chunks"
        return T.make_prefill(self.cfg)(self.params, batch, max_len)

    # ------------------------------------------------------------------
    def generate_greedy(self, prompt, max_new_tokens: int, *,
                        prefill_chunk: Optional[int] = None,
                        extras=None) -> np.ndarray:
        """Greedy decode of one prompt (1, S) — the parity oracle loop
        shared by ``generate_plain`` and the tests.  Plain plane only
        (the offload engine drives the packed planes with stats/usage
        accounting around the same Executor calls).  ``extras`` carries
        non-token conditioning (enc-dec ``audio_embeds``)."""
        assert not self.packed
        prompt = jnp.asarray(prompt)
        max_len = int(prompt.shape[1]) + max_new_tokens
        pre_logits, state, _ = self.prefill(prompt, max_len,
                                            chunk=prefill_chunk,
                                            extras=extras)
        first = jnp.argmax(pre_logits[:, -1], axis=-1)
        out = [int(first[0])]
        tok = first[:, None].astype(jnp.int32)
        for _ in range(max_new_tokens - 1):
            logits, state, _, _ = self.decode(state, tok)
            nxt = jnp.argmax(logits[:, -1], axis=-1)
            tok = nxt[:, None].astype(jnp.int32)
            out.append(int(nxt[0]))
        return np.asarray(out)[None]
