"""Model assembly for every architecture family.

A model is a stack of *blocks*; each block = mixer (attention / SWA /
cross-attn / RG-LRU / mLSTM / sLSTM) + optional FFN (dense MLP or MoE),
pre-norm residual.  The stack is organised for ``lax.scan``:

* ``params["stack"][p]`` — parameters of pattern-position ``p``, stacked
  over the ``n_periods`` repetitions (leading axis), scanned at apply time;
* ``params["tail"]``     — remainder layers (n_layers % period), unscanned.

Apply modes
-----------
* :func:`forward_train`  — full-sequence teacher-forced logits (+MoE aux).
* :func:`prefill`        — same compute, additionally returns a filled
  decode state (KV caches / recurrent states).
* :func:`decode_step`    — one token with the decode state.

Decode-vs-train parity is the key invariant (tests/test_parity.py).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, parse_block
from repro.models import layers as L
from repro.models import moe as M
from repro.models import recurrent as R
from repro.sharding.specs import constrain

PyTree = Any


# ======================================================================
# Init
def _init_block(rng, cfg: ModelConfig, kind: str):
    mixer, ffn = parse_block(kind)
    ks = jax.random.split(rng, 4)
    p: Dict[str, PyTree] = {"norm1": L.init_norm(cfg)}
    if mixer in ("attn", "swa", "xattn", "encattn"):
        p["attn"] = L.init_attention(ks[0], cfg)
    elif mixer == "rglru":
        p["rglru"] = R.init_rglru(ks[0], cfg)
    elif mixer == "mlstm":
        p["mlstm"] = R.init_mlstm(ks[0], cfg)
    elif mixer == "slstm":
        p["slstm"] = R.init_slstm(ks[0], cfg)
    if mixer == "xattn":
        p["norm_x"] = L.init_norm(cfg)
        p["xattn"] = L.init_attention(ks[2], cfg, cross=True)
    if ffn == "mlp":
        p["norm2"] = L.init_norm(cfg)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif ffn == "moe":
        p["norm2"] = L.init_norm(cfg)
        p["moe"] = M.init_moe(ks[1], cfg)
    return p


def init_model(rng, cfg: ModelConfig) -> PyTree:
    ks = jax.random.split(rng, 8)
    params: Dict[str, PyTree] = {"embed": L.init_embedding(ks[0], cfg)}
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_lm_head(ks[1], cfg)
    params["final_norm"] = L.init_norm(cfg)

    period = cfg.pattern_period
    n_p = cfg.n_periods

    def stacked_init(kind, base_key):
        def one(k):
            return _init_block(k, cfg, kind)
        return jax.vmap(one)(jax.random.split(base_key, n_p))

    stack_keys = jax.random.split(ks[2], period)
    params["stack"] = [stacked_init(cfg.block_pattern[i], stack_keys[i])
                       for i in range(period)]
    tail_keys = jax.random.split(ks[3], max(1, cfg.n_tail_layers))
    params["tail"] = [_init_block(tail_keys[i], cfg, k)
                      for i, k in enumerate(cfg.tail_kinds())]

    if cfg.is_encoder_decoder:
        enc_cfg = cfg.replace(block_pattern=("encattn+mlp",),
                              n_layers=cfg.encoder_layers)
        enc_keys = jax.random.split(ks[4], 1)[0]
        def enc_one(k):
            return _init_block(k, enc_cfg, "encattn+mlp")
        params["encoder"] = {
            "stack": [jax.vmap(enc_one)(jax.random.split(enc_keys, cfg.encoder_layers))],
            "final_norm": L.init_norm(cfg),
        }
    if cfg.num_image_tokens:
        params["img_proj"] = (jax.random.normal(ks[5], (cfg.d_model, cfg.d_model))
                              * (cfg.d_model ** -0.5)).astype(jnp.dtype(cfg.dtype))
    return params


def count_params_analytic(cfg: ModelConfig) -> int:
    import math as _math
    shapes = jax.eval_shape(lambda: init_model(jax.random.key(0), cfg))
    return sum(_math.prod(l.shape) for l in jax.tree.leaves(shapes))


# ======================================================================
# Block apply (train / prefill)
def _block_train(p, cfg: ModelConfig, kind: str, x, positions, *,
                 want_state: bool, enc_out=None, enc_pos=None,
                 batch_for_state: int = 0, max_len: int = 0, pad_mask=None,
                 moe_ffn_fn=None):
    """Returns (x, state_or_None, aux).

    ``positions`` is (S,) shared or (B, S) per-row; ``pad_mask`` (B, S)
    marks real tokens (attention mixers only — recurrent mixers process
    pads and callers must not left-pad recurrent archs).  ``moe_ffn_fn``
    overrides the MoE expert computation (packed-offload prefill streams
    experts from the host store this way — DESIGN.md §6).
    """
    mixer, ffn = parse_block(kind)
    aux = {}
    state = {}

    def seq_shard(t):
        # Megatron-SP: keep residual adds sequence-sharded so the backward
        # of TP output projections reduce-scatters instead of all-reducing
        # (§Perf iteration 2 on the 104B train config)
        if cfg.act_seq_shard:
            return constrain(t, ("pod", "data"), "model", None)
        return t

    h = L.apply_norm(p["norm1"], cfg, x)
    if mixer in ("attn", "swa", "encattn"):
        window = cfg.sliding_window if mixer == "swa" else None
        causal = mixer != "encattn"
        if want_state and causal:
            # compute and also fill the rolling KV cache for decode
            y, kvstate = _attn_train_with_cache(p["attn"], cfg, h, positions,
                                                window, max_len,
                                                pad_mask=pad_mask)
            state["kv"] = kvstate
        else:
            y = L.attention_train(p["attn"], cfg, h, positions,
                                  window=window, causal=causal,
                                  pad_mask=pad_mask)
    elif mixer == "xattn":
        window = None
        if want_state:
            y, kvstate = _attn_train_with_cache(p["attn"], cfg, h, positions,
                                                None, max_len,
                                                pad_mask=pad_mask)
            state["kv"] = kvstate
        else:
            y = L.attention_train(p["attn"], cfg, h, positions, window=None,
                                  pad_mask=pad_mask)
    elif mixer == "rglru":
        y, st = R.rglru_train(p["rglru"], cfg, h)
        if want_state:
            state["rec"] = st
    elif mixer == "mlstm":
        y, st = R.mlstm_train(p["mlstm"], cfg, h)
        if want_state:
            state["rec"] = st
    elif mixer == "slstm":
        y, st = R.slstm_train(p["slstm"], cfg, h)
        if want_state:
            state["rec"] = st
    x = x + seq_shard(y)
    if mixer == "xattn":
        hx = L.apply_norm(p["norm_x"], cfg, x)
        y = L.attention_train(p["xattn"], cfg, hx, positions,
                              kv_override=enc_out, kv_positions=enc_pos)
        x = x + seq_shard(y)
    if ffn != "none":
        h2 = L.apply_norm(p["norm2"], cfg, x)
        if ffn == "mlp":
            x = x + seq_shard(L.apply_mlp(p["mlp"], cfg, h2))
        else:
            B, S, D = h2.shape
            y2d, moe_aux = M.moe_apply_dispatch(
                p["moe"], cfg, h2.reshape(B * S, D),
                token_mask=(pad_mask.reshape(B * S)
                            if pad_mask is not None else None),
                expert_ffn_fn=moe_ffn_fn)
            aux.update(moe_aux)
            x = x + seq_shard(y2d.reshape(B, S, D))
    return x, (state if want_state else None), aux


def _attn_train_with_cache(p, cfg, h, positions, window, max_len,
                           pad_mask=None):
    """Full-seq attention that also produces the decode KV cache.

    With a left-pad ``pad_mask``, pad entries carry pos = −1 and land in
    ring slots that real entries never occupy (real logical positions of
    a row with R real tokens fill slots 0..min(R,W)−1; pads only appear
    in the written tail when R < W, so the pads' slot mod(−1, W) = W−1
    is free).  Decode then naturally skips them via the pos >= 0 mask.
    """
    B, S, _ = h.shape
    y = L.attention_train(p, cfg, h, positions, window=window,
                          pad_mask=pad_mask)
    cache = L.init_attn_cache(cfg, B, max_len, window)
    W = cache["k"].shape[1]
    k_full, v_full = L._project_kv(p, cfg, h)
    k_full = L.apply_rope(k_full, positions, cfg)
    n = min(W, S)
    pos2 = jnp.broadcast_to(positions, (B, S)) if positions.ndim == 1 \
        else positions
    tail_pos = pos2[:, -n:]
    if pad_mask is not None:
        tail_pos = jnp.where(pad_mask[:, -n:], tail_pos, -1)
    slots = jnp.mod(tail_pos, W)  # (B, n); pos −1 (pads) -> slot W−1
    bidx = jnp.arange(B)[:, None]
    cache = {
        "k": cache["k"].at[bidx, slots].set(k_full[:, -n:]),
        "v": cache["v"].at[bidx, slots].set(v_full[:, -n:]),
        "pos": cache["pos"].at[bidx, slots].set(tail_pos.astype(jnp.int32)),
    }
    return y, cache


# ======================================================================
# Block decode (single token)
def _mixer_decode(p, cfg: ModelConfig, kind: str, x_t, state, pos, *,
                  enc_kv=None, pages=None, active=None, layer=None):
    """Mixer half of one block's decode step (norm1 + mixer + residual,
    plus the cross-attention sub-block for enc-dec decoders).  Shared by
    the scanned :func:`decode_step` and the layerwise packed-offload
    driver (:func:`decode_block_packed`) so both run the exact same
    non-MoE computation.  ``pages``/``active`` select the paged KV plane
    (DESIGN.md §9) — ignored by the dense ring caches; ``layer`` marks a
    layer-stacked paged cache addressed in place (scan-carry path)."""
    mixer, _ = parse_block(kind)
    h = L.apply_norm(p["norm1"], cfg, x_t)
    if mixer in ("attn", "swa", "xattn"):
        window = cfg.sliding_window if mixer == "swa" else None
        y, kv = L.attention_decode(p["attn"], cfg, h, state["kv"], pos,
                                   window=window, pages=pages,
                                   active=active, layer=layer)
        state = dict(state, kv=kv)
    elif mixer == "rglru":
        fn = R.rglru_decode if x_t.shape[1] == 1 else R.rglru_chunk
        y, rec = fn(p["rglru"], cfg, h, state["rec"])
        state = dict(state, rec=rec)
    elif mixer == "mlstm":
        fn = R.mlstm_decode if x_t.shape[1] == 1 else R.mlstm_chunk
        y, rec = fn(p["mlstm"], cfg, h, state["rec"])
        state = dict(state, rec=rec)
    elif mixer == "slstm":
        fn = R.slstm_decode if x_t.shape[1] == 1 else R.slstm_chunk
        y, rec = fn(p["slstm"], cfg, h, state["rec"])
        state = dict(state, rec=rec)
    x_t = x_t + y
    if mixer == "xattn":
        hx = L.apply_norm(p["norm_x"], cfg, x_t)
        ek, ev, ep = enc_kv
        y = L.cross_attention_decode(p["xattn"], cfg, hx, ek, ev, ep)
        x_t = x_t + y
    return x_t, state


def _block_decode(p, cfg: ModelConfig, kind: str, x_t, state, pos, *,
                  enc_kv=None, moe_mode: str = "dispatch", offload_hook=None,
                  pages=None, active=None, layer=None):
    mixer, ffn = parse_block(kind)
    info = {}
    x_t, state = _mixer_decode(p, cfg, kind, x_t, state, pos, enc_kv=enc_kv,
                               pages=pages, active=active, layer=layer)
    if ffn != "none":
        h2 = L.apply_norm(p["norm2"], cfg, x_t)
        B, S, D = h2.shape
        h2d = h2.reshape(B * S, D)
        if ffn == "moe":
            if moe_mode == "gather" or offload_hook is not None:
                y2d, route = M.moe_apply_gather(p["moe"], cfg, h2d)
                info["route"] = route
                info["hidden_pre_moe"] = h2d
            else:
                y2d, _ = M.moe_apply_dispatch(p["moe"], cfg, h2d)
        else:
            y2d = L.apply_mlp(p["mlp"], cfg, h2).reshape(B * S, D)
        x_t = x_t + y2d.reshape(B, S, D)
    return x_t, state, info


def decode_block_packed(p, cfg: ModelConfig, kind: str, x_t, state, pos,
                        store, pstate, l_moe, routers, *, lookahead: int = 1,
                        n_spec: int = 0, fused: bool = True, active=None,
                        vectorized: bool = True, pages=None):
    """One block's decode step with MoE served from the packed expert
    buffer pool — ``moe_mode="packed"`` (DESIGN.md §6).  Identical mixer
    computation to :func:`_block_decode`; the MoE FFN reads HQQ-packed
    slots through :func:`repro.models.moe.moe_apply_packed` and threads
    the pool state through.  Returns (x_t, state, pstate, info).

    This is the *synchronous* one-dispatch-per-block form (staging, when
    ``n_spec > 0``, runs inside the same jitted program as the compute) —
    the pipelined driver instead splits mixer / MoE / staging into
    separate dispatches (:func:`decode_block_packed_mixer` /
    :func:`decode_block_packed_moe`, DESIGN.md §7)."""
    mixer, ffn = parse_block(kind)
    info = {}
    x_t, state = _mixer_decode(p, cfg, kind, x_t, state, pos, pages=pages,
                               active=active)
    if ffn != "none":
        h2 = L.apply_norm(p["norm2"], cfg, x_t)
        B, S, D = h2.shape
        h2d = h2.reshape(B * S, D)
        if ffn == "moe":
            # acquire masks per ROW of the (B*S, D) token matrix; expand
            # the per-slot mask across chunk positions (C=1 unchanged)
            act_tok = active if (active is None or S == 1) \
                else jnp.repeat(active, S)
            y2d, route, pstate = M.moe_apply_packed(
                p["moe"], cfg, h2d, store, pstate, l_moe, routers,
                lookahead=lookahead, n_spec=n_spec, fused=fused,
                active=act_tok, vectorized=vectorized)
            info["route"] = route
            info["hidden_pre_moe"] = h2d
        else:
            y2d = L.apply_mlp(p["mlp"], cfg, h2).reshape(B * S, D)
        x_t = x_t + y2d.reshape(B, S, D)
    return x_t, state, pstate, info


def decode_block_packed_mixer(p, cfg: ModelConfig, kind: str, x_t, state,
                              pos, pages=None, active=None):
    """Mixer half of a packed MoE block's decode step (pipelined driver,
    DESIGN.md §7): norm1 + mixer + residual plus the pre-MoE norm —
    everything that does NOT read the expert pool state, so this dispatch
    can execute while the previous layer's speculative staging transfer
    is still in flight.  Returns (x_t, state, h2 (B, S, D))."""
    x_t, state = _mixer_decode(p, cfg, kind, x_t, state, pos, pages=pages,
                               active=active)
    return x_t, state, L.apply_norm(p["norm2"], cfg, x_t)


def decode_block_packed_moe(p, cfg: ModelConfig, x_t, h2, store, pstate,
                            l_moe, *, fused: bool = True,
                            vectorized: bool = True, active=None):
    """MoE half of a packed block's decode step (pipelined driver): route
    + ``acquire`` + packed compute + residual.  The FIRST op that reads
    the pool state — the fence where the previous layer's asynchronously
    dispatched staging is consumed (DESIGN.md §7).  Staging itself is NOT
    performed here (``n_spec=0``); the driver dispatches it separately.
    Returns (x_t, pstate, info)."""
    B, S, D = h2.shape
    h2d = h2.reshape(B * S, D)
    act_tok = active if (active is None or S == 1) else jnp.repeat(active, S)
    y2d, route, pstate = M.moe_apply_packed(
        p["moe"], cfg, h2d, store, pstate, l_moe, None, n_spec=0,
        fused=fused, active=act_tok, vectorized=vectorized)
    x_t = x_t + y2d.reshape(B, S, D)
    return x_t, pstate, {"route": route, "hidden_pre_moe": h2d}


# ======================================================================
# Decode-state init
def _block_state(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                 paged=None):
    mixer, _ = parse_block(kind)
    if mixer in ("attn", "xattn", "swa") and paged is not None:
        return {"kv": L.init_paged_attn_cache(cfg, *paged)}
    if mixer in ("attn", "xattn"):
        return {"kv": L.init_attn_cache(cfg, batch, max_len, None)}
    if mixer == "swa":
        return {"kv": L.init_attn_cache(cfg, batch, max_len, cfg.sliding_window)}
    if mixer == "rglru":
        return {"rec": R.init_rglru_state(cfg, batch)}
    if mixer == "mlstm":
        return {"rec": R.init_mlstm_state(cfg, batch)}
    if mixer == "slstm":
        return {"rec": R.init_slstm_state(cfg, batch)}
    return {}


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, *,
                      kv_pages: int = None, kv_page: int = None,
                      kv_max_pages: int = None) -> PyTree:
    """``kv_pages``/``kv_page``/``kv_max_pages`` switch the KV plane to
    block-paged storage (DESIGN.md §9): every attention layer holds a
    batch-free pool of ``kv_pages`` pages of ``kv_page`` positions and
    the state grows a per-row page table ``state["pages"]``
    ((batch, kv_max_pages), −1 = unallocated) shared by all layers —
    which is why the whole pool serves any batch size (a B=1 admission
    chunk writes the same pages the running batch reads)."""
    # Per-layer-kind state planes (DESIGN.md §12): only "kv" layers take
    # the paged layout — recurrent layers keep their fixed-size state
    # (the degenerate one-page-per-slot case) whether or not the config
    # is paged, and a pure-recurrent stack simply has an all-dense state
    # plus an (unused) page table.
    paged = (kv_pages, kv_page) if kv_page is not None else None

    def stacked(kind):
        one = _block_state(cfg, kind, batch, max_len, paged)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape).copy(), one)

    state: Dict[str, PyTree] = {
        "stack": [stacked(k) for k in cfg.block_pattern],
        "tail": [_block_state(cfg, k, batch, max_len, paged)
                 for k in cfg.tail_kinds()],
        "pos": jnp.zeros((), jnp.int32),
    }
    if paged is not None:
        state["pages"] = jnp.full((batch, kv_max_pages), -1, jnp.int32)
    if cfg.is_encoder_decoder:
        dt = jnp.dtype(cfg.dtype)
        S_e = cfg.encoder_seq
        state["enc_kv"] = {
            "k": jnp.zeros((cfg.n_layers, batch, S_e, cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((cfg.n_layers, batch, S_e, cfg.n_kv_heads, cfg.head_dim), dt),
            "pos": jnp.broadcast_to(jnp.arange(S_e, dtype=jnp.int32),
                                    (cfg.n_layers, S_e)).copy(),
        }
    return state


# ======================================================================
# Embedding frontends
def _embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    x = L.embed(params["embed"], cfg, batch["tokens"])
    if cfg.num_image_tokens and "image_embeds" in batch:
        img = jnp.einsum("bnd,de->bne", batch["image_embeds"].astype(x.dtype),
                         params["img_proj"])
        n = img.shape[1]
        x = jnp.concatenate([img, x[:, n:]], axis=1)
    return x


def _run_encoder(params, cfg: ModelConfig, audio_embeds, remat=False):
    """Whisper-style encoder over stub frontend embeddings."""
    x = audio_embeds.astype(jnp.dtype(cfg.dtype))
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)

    def body(carry, pslice):
        h, _, _ = _block_train(pslice, cfg, "encattn+mlp", carry, pos,
                               want_state=False)
        return h, ()

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["encoder"]["stack"][0])
    return L.apply_norm(params["encoder"]["final_norm"], cfg, x), pos


# ======================================================================
# Forward (train) and prefill
def pad_positions(pad_mask, S: int):
    """Prefill position layout shared by every prefill driver (scanned
    ``forward_train`` and the packed layerwise prefill): with a left-pad
    mask, real token j of a row gets logical position j − n_pads (rows
    start at position 0 regardless of padding) and pads get −1, masking
    them out of every attention; without one, plain ``arange``.
    Returns (pad_mask as bool or None, positions)."""
    if pad_mask is None:
        return None, jnp.arange(S, dtype=jnp.int32)
    pad_mask = pad_mask.astype(bool)
    positions = jnp.cumsum(pad_mask.astype(jnp.int32), axis=1) - 1
    return pad_mask, jnp.where(pad_mask, positions, -1)


def forward_train(params, cfg: ModelConfig, batch, *, want_state=False,
                  max_len: int = 0, remat: bool = False):
    x = _embed_inputs(params, cfg, batch)
    B, S, _ = x.shape
    x = constrain(x, ("pod", "data"), None, None)
    pad_mask, positions = pad_positions(batch.get("pad_mask"), S)
    max_len = max_len or S

    enc_out = enc_pos = None
    if cfg.is_encoder_decoder:
        enc_out, enc_pos = _run_encoder(params, cfg, batch["audio_embeds"],
                                        remat=remat and not want_state)

    aux_acc = {"load_balance": jnp.zeros((), jnp.float32)}
    period = cfg.pattern_period
    states = {"stack": [], "tail": []}

    # scan over periods; inside the body apply each pattern position once
    def body(carry, pslices):
        x, aux_lb = carry
        if cfg.act_seq_shard:
            # sequence-parallel residual stream (shards the remat stack)
            x = constrain(x, ("pod", "data"), "model", None)
        st_out = []
        for i in range(period):
            kind = cfg.block_pattern[i]
            x, st, aux = _block_train(pslices[i], cfg, kind, x, positions,
                                      want_state=want_state, enc_out=enc_out,
                                      enc_pos=enc_pos, max_len=max_len,
                                      pad_mask=pad_mask)
            if "load_balance" in aux:
                aux_lb = aux_lb + aux["load_balance"]
            st_out.append(st if st is not None else {})
        return (x, aux_lb), tuple(st_out)

    if remat and not want_state:
        # activation checkpointing: save only the per-period residual
        # stream; everything inside a period is recomputed in the bwd pass
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    (x, lb), stacked_states = jax.lax.scan(
        body, (x, aux_acc["load_balance"]), tuple(params["stack"]))
    if want_state:
        states["stack"] = list(stacked_states)

    for i, kind in enumerate(cfg.tail_kinds()):
        x, st, aux = _block_train(params["tail"][i], cfg, kind, x, positions,
                                  want_state=want_state, enc_out=enc_out,
                                  enc_pos=enc_pos, max_len=max_len,
                                  pad_mask=pad_mask)
        if "load_balance" in aux:
            lb = lb + aux["load_balance"]
        if want_state:
            states["tail"].append(st)

    x = L.apply_norm(params["final_norm"], cfg, x)
    logits = L.unembed(params, cfg, x)
    aux_acc["load_balance"] = lb
    if want_state:
        # per-row decode positions when rows have different true lengths
        states["pos"] = (pad_mask.sum(1).astype(jnp.int32)
                         if pad_mask is not None
                         else jnp.asarray(S, jnp.int32))
        if cfg.is_encoder_decoder:
            states["enc_kv"] = _collect_enc_kv(params, cfg, enc_out)
        return logits, aux_acc, states
    return logits, aux_acc


def _collect_enc_kv(params, cfg, enc_out):
    """Precompute per-decoder-layer cross-attn K/V from encoder output."""
    def per_layer(pslice):
        k, v = L.precompute_cross_kv(pslice["xattn"], cfg, enc_out)
        return k, v

    ks, vs = jax.vmap(per_layer)(params["stack"][0])
    S_e = enc_out.shape[1]
    return {"k": ks, "v": vs,
            "pos": jnp.broadcast_to(jnp.arange(S_e, dtype=jnp.int32),
                                    (cfg.n_layers, S_e)).copy()}


def encode_enc_kv(params, cfg: ModelConfig, audio_embeds):
    """Encoder pass + per-decoder-layer cross-attn K/V — the admission-
    time computation of the read-only shared encoder-KV plane
    (DESIGN.md §12): run ONCE per request when it is admitted, referenced
    by every decode step, never scattered to.  ``audio_embeds``:
    (B, encoder_seq, d_model)."""
    enc_out, _ = _run_encoder(params, cfg, audio_embeds)
    return _collect_enc_kv(params, cfg, enc_out)


def prefill(params, cfg: ModelConfig, batch, max_len: int):
    """``batch`` may carry ``pad_mask`` (B, S) for left-padded prompts of
    unequal length; the returned state then has per-row ``pos`` (B,)."""
    logits, aux, state = forward_train(params, cfg, batch, want_state=True,
                                       max_len=max_len)
    return logits, state


_ENGINE_JIT_CACHE: Dict[Any, Any] = {}
_ENGINE_JIT_STATS: Dict[str, int] = {"builds": 0, "hits": 0}


def cached_jit(key, make):
    """Process-wide cache of engine-level jitted callables.

    Engines (serving, offload, oracle decoders) are constructed per
    test / benchmark pass; per-instance ``jax.jit`` closures would
    recompile byte-identical programs every time (jax caches by function
    *object*).  Keying on the (hashable, frozen) config plus mode flags
    lets every instance share one executable — a large share of the
    tier-1 suite's runtime was exactly this recompilation (DESIGN.md §7).
    ``params``/state always ride as call arguments, so nothing model-
    specific is baked into the cache entry.
    """
    if key not in _ENGINE_JIT_CACHE:
        _ENGINE_JIT_STATS["builds"] += 1
        _ENGINE_JIT_CACHE[key] = make()
    else:
        _ENGINE_JIT_STATS["hits"] += 1
    return _ENGINE_JIT_CACHE[key]


def cached_jit_stats() -> Dict[str, Any]:
    """Introspection for the engine-executable cache (DESIGN.md §8):
    ``builds`` counts ``make()`` invocations (one per distinct program
    key per process — the compile-once invariant the runtime executor's
    tests assert), ``hits`` the cache reuses, ``entries``/``keys`` the
    live cache contents."""
    return {**_ENGINE_JIT_STATS,
            "entries": len(_ENGINE_JIT_CACHE),
            "keys": list(_ENGINE_JIT_CACHE.keys())}


def cached_jit_clear() -> None:
    """Drop every cached engine executable (and its stats).

    The explicit hook conftest uses after memory-heavy test modules:
    ``jax.clear_caches()`` invalidates the underlying XLA executables,
    but the jitted *wrappers* held here would pin their constants/params
    closures alive — clearing both releases the memory and resets the
    compile-once accounting for the next measurement."""
    _ENGINE_JIT_CACHE.clear()
    _ENGINE_JIT_STATS["builds"] = 0
    _ENGINE_JIT_STATS["hits"] = 0


def make_prefill(cfg: ModelConfig):
    """Jitted prefill with static ``max_len`` — the one wrapper every
    engine shares: ``fn(params, batch, max_len)``."""
    return cached_jit(
        ("prefill", cfg),
        lambda: jax.jit(lambda p, b, ml: prefill(p, cfg, b, ml),
                        static_argnums=2))


# ======================================================================
# Decode
def decode_step(params, cfg: ModelConfig, state, tokens, *,
                moe_mode: str = "dispatch", collect_info: bool = False,
                active=None, row=None):
    """tokens: (B, C) int32. Returns (logits (B,C,V), new_state[, infos]).

    C = 1 is the classic one-token decode step.  C > 1 is a *prefill
    chunk*: attention mixers write the chunk's K/V into the caches at
    positions ``pos .. pos+C-1``; recurrent mixers (rglru/mlstm/slstm)
    fold the chunk through their sequential chunk forms
    (``repro.models.recurrent.*_chunk`` — carry composition is exact, so
    chunk splits are bitwise-invariant); enc-dec decoders additionally
    read the shared ``state["enc_kv"]`` plane.  ``pos`` advances by C —
    the runtime executor drives chunked prefill through exactly this
    step (DESIGN.md §8/§12), so decode and chunked prefill share one
    block program for EVERY layer kind in the config zoo.

    ``state["pos"]`` may be a scalar (whole batch in lock-step) or (B,)
    per-row positions (continuous batching / padded prefill).

    ``moe_mode``: "dispatch" (scatter into capacity buffers), "gather"
    (per-token expert-weight gather — interactive decode / routing
    collection).  The third mode, "packed" (HQQ-packed experts served
    from the device buffer pool), runs through the layerwise driver
    (``repro.runtime.Executor`` packed planes ->
    :func:`decode_block_packed`) rather than this scanned step, because
    its slot state threads across layers; on this backend the layerwise
    loop is bitwise-identical to the scan (tests/test_offload.py).

    Paged-KV states (``"pages"`` in state, DESIGN.md §9) add two
    controls: ``active`` (B,) bool gates which rows write KV and advance
    ``pos`` (idle / mid-admission slots are frozen — their pages are
    either unallocated or being filled by chunk programs), and
    ``row`` (traced int32 scalar) runs the step as a **B=1 row chunk**
    against the shared page pools: tokens must be (1, C), the program
    slices that row's page-table row and position, writes the chunk's KV
    straight into the pool pages the row owns (no private accumulator
    state, no install copy), and advances only that row's ``pos``."""
    if moe_mode == "packed":
        raise ValueError(
            "moe_mode='packed' threads buffer-pool state across layers; "
            "drive it with a packed-plane repro.runtime.Executor "
            "(layerwise decode_block_packed), not the scanned decode_step")
    x = L.embed(params["embed"], cfg, tokens)
    pages = state.get("pages")
    if row is not None:
        assert pages is not None, "row chunks need a paged-KV state"
        row = jnp.asarray(row, jnp.int32)
        pages = jax.lax.dynamic_slice(pages, (row, 0),
                                      (1, pages.shape[1]))
        pos = jax.lax.dynamic_slice(state["pos"], (row,), (1,))
    else:
        pos = state["pos"]
    period = cfg.pattern_period
    infos = []

    enc_kv_stacked = state.get("enc_kv")

    def _enc_kv_for(li):
        """Per-layer cross-attn view of the shared encoder-KV plane —
        READ-ONLY (computed once at admission, never scattered to)."""
        ek, ev = enc_kv_stacked["k"][li], enc_kv_stacked["v"][li]
        if row is not None:
            ek = jax.lax.dynamic_slice_in_dim(ek, row, 1, axis=0)
            ev = jax.lax.dynamic_slice_in_dim(ev, row, 1, axis=0)
        return ek, ev, enc_kv_stacked["pos"][li]

    def _gate_rows(old, new):
        """Freeze inactive rows' fixed-size (rec) state: unlike the ring
        caches — where a frozen row's writes stay row-local and invisible
        behind its pos — a recurrent carry update would corrupt the row,
        so masked rows keep their pre-step state bit for bit."""
        return jax.tree.map(
            lambda o, n: jnp.where(
                active.reshape(active.shape + (1,) * (n.ndim - 1)), n, o),
            old, new)

    # The stacked decode state rides in the scan CARRY and is updated
    # in place with dynamic_update_index — passing it as xs/ys would make
    # XLA double-buffer the entire KV stack (2.5x cache memory at
    # decode_32k; caught by the dry-run).
    def scan_body(carry, xs):
        x, sstacks = carry
        pslices, lidx = xs
        new_stacks = list(sstacks)
        inf_out = []
        for i in range(period):
            kind = cfg.block_pattern[i]
            mixer = parse_block(kind)[0]
            enc_kv = None
            if mixer == "xattn" and enc_kv_stacked is not None:
                enc_kv = _enc_kv_for(lidx * period + i)
            if pages is not None and mixer in ("attn", "swa", "xattn"):
                # paged KV: the layer-stacked pool stays WHOLE in the
                # carry; the layer index rides in the scatter/gather
                # indices, so XLA updates the (donated) pool in place —
                # slicing it per layer would copy pool-capacity bytes
                # every step (DESIGN.md §9)
                x, st, info = _block_decode(pslices[i], cfg, kind, x,
                                            new_stacks[i], pos,
                                            enc_kv=enc_kv,
                                            moe_mode=moe_mode, pages=pages,
                                            active=active, layer=lidx)
                new_stacks[i] = st
            else:
                # dense rings and fixed-size recurrent state (the
                # DESIGN.md §12 "rec" plane — also taken by rec layers of
                # a paged hybrid: their state never pages)
                sslice = stack_layer(new_stacks[i], lidx)
                blk_in = sslice
                if row is not None:
                    blk_in = jax.tree.map(
                        lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1,
                                                               axis=0),
                        sslice)
                x, st, info = _block_decode(pslices[i], cfg, kind, x,
                                            blk_in, pos, enc_kv=enc_kv,
                                            moe_mode=moe_mode)
                if row is not None:
                    st = jax.tree.map(
                        lambda full, r: jax.lax.dynamic_update_slice_in_dim(
                            full, r, row, axis=0),
                        sslice, st)
                elif active is not None:
                    st = _gate_rows(sslice, st)
                new_stacks[i] = stack_set_layer(new_stacks[i], lidx, st)
            if collect_info:
                inf_out.append(info)
        return (x, tuple(new_stacks)), \
            (tuple(inf_out) if collect_info else ())

    lidx = jnp.arange(cfg.n_periods, dtype=jnp.int32)
    (x, new_stack), info_stack = jax.lax.scan(
        scan_body, (x, tuple(state["stack"])),
        (tuple(params["stack"]), lidx))

    new_tail = []
    for i, kind in enumerate(cfg.tail_kinds()):
        mixer = parse_block(kind)[0]
        enc_kv = None
        if mixer == "xattn" and enc_kv_stacked is not None:
            enc_kv = _enc_kv_for(cfg.n_periods * period + i)
        st_in = state["tail"][i]
        if pages is not None and mixer in ("attn", "swa", "xattn"):
            x, st, info = _block_decode(params["tail"][i], cfg, kind, x,
                                        st_in, pos, enc_kv=enc_kv,
                                        moe_mode=moe_mode,
                                        pages=pages, active=active)
        else:
            blk_in = st_in
            if row is not None:
                blk_in = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=0),
                    st_in)
            x, st, info = _block_decode(params["tail"][i], cfg, kind, x,
                                        blk_in, pos, enc_kv=enc_kv,
                                        moe_mode=moe_mode)
            if row is not None:
                st = jax.tree.map(
                    lambda full, r: jax.lax.dynamic_update_slice_in_dim(
                        full, r, row, axis=0),
                    st_in, st)
            elif active is not None:
                st = _gate_rows(st_in, st)
        new_tail.append(st)
        if collect_info:
            infos.append(info)

    x = L.apply_norm(params["final_norm"], cfg, x)
    logits = L.unembed(params, cfg, x)
    C = tokens.shape[1]
    if row is not None:
        new_pos = jax.lax.dynamic_update_slice(state["pos"], pos + C, (row,))
    elif pages is not None and active is not None:
        # frozen rows (idle slots / mid-admission) must not advance: an
        # admission's next chunk writes at the position it left off
        new_pos = pos + jnp.where(active, C, 0).astype(pos.dtype)
    else:
        new_pos = pos + C
    new_state = dict(state, stack=list(new_stack), tail=new_tail,
                     pos=new_pos)
    if collect_info:
        return logits, new_state, (info_stack, infos)
    return logits, new_state


# ======================================================================
# Per-layer param/state access (used by the offload engine / tracing,
# which run an unscanned python loop over layers).
def layer_params(params, cfg: ModelConfig, layer_idx: int):
    period = cfg.pattern_period
    n_scanned = cfg.n_periods * period
    if layer_idx < n_scanned:
        pos = layer_idx % period
        per = layer_idx // period
        return jax.tree.map(lambda a: a[per], params["stack"][pos])
    return params["tail"][layer_idx - n_scanned]


def layer_kind(cfg: ModelConfig, layer_idx: int) -> str:
    return cfg.block_pattern[layer_idx % cfg.pattern_period]


def stack_layer(stack, per):
    """One layer's decode state out of a layer-stacked state tree, inside
    a program: every leaf indexed at ``per`` (a traced int32 period
    index, so one program serves every period).  ``per=None`` marks a
    tail layer, whose state is not stacked and passes through as is."""
    if per is None:
        return stack
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, per, 0, keepdims=False),
        stack)


def stack_set_layer(stack, per, new):
    """Write one layer's decode state back into the stacked tree at
    ``per`` — in place when the program donates ``stack``.  The inverse
    of :func:`stack_layer` (``per=None``: ``new`` is the tail layer's
    whole state)."""
    if per is None:
        return new
    return jax.tree.map(
        lambda a, b: jax.lax.dynamic_update_index_in_dim(a, b, per, 0),
        stack, new)


def embed_tokens(params, cfg: ModelConfig, tokens):
    """(B, S) int32 -> (B, S, D) embeddings (layerwise-driver frontend)."""
    return L.embed(params["embed"], cfg, tokens)


def apply_head(params, cfg: ModelConfig, x):
    """Final norm + unembed (layerwise-driver backend)."""
    return L.unembed(params, cfg, L.apply_norm(params["final_norm"], cfg, x))
