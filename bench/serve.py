"""Drive the serving program: build it from a configuration file and a
seed, warm up exactly the shapes a mix uses, and run the measured window
closed-loop or open-loop, time-stamping every token the host receives.

The program under test is the packed offload plane
(``OffloadEngine(quantized=True)``) served by ``ContinuousEngine`` with
paged KV and chunked prefill.  Every call into it from the loop sits in a
``jax.profiler.TraceAnnotation`` named ``bench.*`` (see
``trace_reduce.py``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

import generator as G
import weights as W

clock = time.perf_counter


def program_config(config):
    """The program's ModelConfig for a configuration file, checked against
    every size the file states."""
    from repro.configs import get_config
    m, q = config["model"], config["quant"]
    base = get_config(config["registry"])
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in config.get("overrides", {}).items()}
    cfg = base.replace(**over)
    want = {"n_layers": m["n_layers"], "d_model": m["d_model"],
            "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
            "head_dim": m["head_dim"], "d_ff": m["d_ff"],
            "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
            "norm_eps": m["norm_eps"], "dtype": m["dtype"],
            "tie_embeddings": m["tie_embeddings"],
            "sliding_window": m.get("sliding_window"),
            "block_pattern": tuple(m["block_pattern"])}
    got = {k: getattr(cfg, k) for k in want}
    got["sliding_window"] = (cfg.sliding_window if any(
        b.startswith("swa") for b in cfg.block_pattern) else None)
    moe = (cfg.moe.num_experts, cfg.moe.top_k)
    off = cfg.offload
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if moe != (m["num_experts"], m["top_k"]):
        bad["moe"] = (moe, (m["num_experts"], m["top_k"]))
    o = config["offload"]
    if (off.cache_size, off.num_speculative, off.lookahead,
            off.expert_bits, off.attn_bits) != (
            o["cache_size"], o["num_speculative"], o["lookahead"],
            q["experts"]["bits"], q["wo"]["bits"]):
        bad["offload"] = (off, o)
    if cfg.mlp_act != "swiglu" or cfg.norm != "rmsnorm" or \
            cfg.rope_fraction != 1.0 or cfg.qkv_bias or cfg.logit_softcap:
        bad["architecture"] = "not a plain SwiGLU/RMSNorm/RoPE block"
    if bad:
        raise ValueError(f"{config['name']}: the program's configuration "
                         f"differs from the file: {bad}")
    return cfg


def build(config, mix, seed: int, telemetry=None):
    """(engine, offload engine): seeded weights made on the device, the
    packed expert store built from them, the weights dropped."""
    from repro.core.offload_engine import OffloadEngine
    from repro.models import transformer as T
    from repro.serving.engine import ContinuousEngine
    from repro.serving.sampler import SamplerConfig
    cfg = program_config(config)
    params = W.program_params(config["model"], seed)
    want = jax.eval_shape(lambda: T.init_model(jax.random.key(0), cfg))
    if jax.tree.map(lambda a: (a.shape, a.dtype), want) != jax.tree.map(
            lambda a: (a.shape, a.dtype), params):
        raise ValueError("seeded weights do not match the program's "
                         "parameter layout")
    off = OffloadEngine(params, cfg, cfg.offload, quantized=True)
    del params
    eng_kw = mix["engine"]
    slot_len = (G.length_range(mix["prompt"])[1]
                + G.length_range(mix["output"])[1])
    cont = ContinuousEngine(
        off.params, cfg, max_slots=eng_kw["max_slots"], slot_len=slot_len,
        sampler=SamplerConfig(kind="categorical",
                              temperature=float(mix["temperature"])),
        eos_id=None, prefill_chunk=config["serving"]["prefill_chunk"],
        seed=int(np.random.SeedSequence(int(seed)).generate_state(3)[2]
                 & 0x7FFFFFFF),
        offload=off, kv_page=eng_kw["kv_page"], telemetry=telemetry)
    return cont, off


class Loop:
    """Submits :class:`generator.Request` s to the engine and records, per
    request, when each token reached the host, and per engine step, the
    positions of the tokens it decoded."""

    def __init__(self, cont, temperature: float):
        self.cont = cont
        self.temperature = float(temperature)
        self.by_rid = {}
        self.steps = []  # (end time, [positions of the tokens decoded])
        self._pos = None

    def keep_logits(self):
        """From now on keep, for greedy requests, the logits rows the
        engine samples from: references to its own device arrays, no copy
        and no sync.  Only the correctness check, after the window, turns
        this on."""
        sample_rows = self.cont._sample_rows

        def keep(logits, reqs):
            toks = sample_rows(logits, reqs)
            for i, gr in enumerate(reqs):
                r = self.by_rid.get(gr.rid)
                if r is not None and r.greedy:
                    r.logits.append((logits, i))
            return toks
        self.cont._sample_rows = keep

    def cancel_all(self):
        """End every request still queued or running (between steps)."""
        for r in self.by_rid.values():
            if not r.status and self.cont.cancel(r.rid):
                r.status, r.finished = "cancelled", clock()

    def _on_token(self, gr, tok):
        r = self.by_rid[gr.rid]
        i = len(r.tokens)
        r.token_times.append(clock())
        r.tokens.append(int(tok))
        if i and self._pos is not None:
            self._pos.append(len(r.prompt) + i - 1)

    def submit(self, r, now=None):
        with jax.profiler.TraceAnnotation("bench.submit"):
            r.submitted = clock() if now is None else now
            gr = self.cont.submit(
                r.prompt, r.max_new, on_token=self._on_token,
                temperature=0.0 if r.greedy else self.temperature)
            self.by_rid[gr.rid] = r
            r.rid = gr.rid
            if gr.status is not None:  # refused at the door
                r.status = gr.status
                r.finished = clock()
        return gr

    def busy(self) -> bool:
        s = self.cont.sched
        return bool(s.has_waiting or s.n_running)

    def step(self):
        self._pos = []
        with jax.profiler.TraceAnnotation("bench.step"):
            done = self.cont.step()
        t = clock()
        with jax.profiler.TraceAnnotation("bench.host"):
            for gr in done:
                r = self.by_rid[gr.rid]
                r.status, r.finished = gr.status, t
            self.steps.append((t, self._pos))
        self._pos = None
        return done

    def drain(self, deadline: float):
        while self.busy() and clock() < deadline:
            self.step()


def run_closed(loop, reqs, clients: int, t_end: float):
    """Closed loop: each client sends its next request when its last one
    finished; stops at ``t_end``."""
    it = iter(reqs)
    inflight = 0
    while clock() < t_end:
        while inflight < clients:
            r = next(it)
            r.due = clock()
            loop.submit(r, r.due)
            inflight += 1
        inflight -= len(loop.step())


def run_open(loop, reqs, t_first: float, t_end: float):
    """Open loop: request i is due at ``t_first`` plus the sum of the gaps
    before it, sent then whatever the engine is doing; stops at
    ``t_end``."""
    due = t_first
    for r in reqs:
        due += r.gap_s
        r.due = due
    i = 0
    while True:
        now = clock()
        if now >= t_end:
            break
        while i < len(reqs) and reqs[i].due <= now:
            loop.submit(reqs[i], clock())
            i += 1
        if loop.busy():
            loop.step()
        else:
            nxt = reqs[i].due if i < len(reqs) else t_end
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, t_end) - clock()))


# ----------------------------------------------------------------------
def warm_requests(mix, chunk: int, vocab: int):
    """Requests that take the engine through every program shape the mix
    can use, and no other: each prompt-chunk length its prompt lengths
    leave, each power-of-two KV page-table width up to its longest
    context, and (for batched engines) every count of decoding rows."""
    rng = np.random.default_rng(12345)
    eng = mix["engine"]
    ps = eng["kv_page"]
    lengths = G.possible_prompt_lengths(mix)
    max_out = G.length_range(mix["output"])[1]
    max_ctx = G.length_range(mix["prompt"])[1] + max_out
    max_pages = -(-max_ctx // ps)

    def bucket(n_tokens):
        used = -(-n_tokens // ps)
        w = 1
        while w < used:
            w *= 2
        return min(w, max_pages)

    want = {}  # (chunk remainder, bucket) -> prompt length
    for p in lengths:
        key = ((p - 1) % chunk + 1, bucket(p + 1))
        want.setdefault(key, p)
    plan = sorted(set(want.values()))
    seen_b = {bucket(p + 1) for p in plan}
    out = [G.Request(index=-1 - i, prompt=rng.integers(0, vocab, p,
                                                       dtype=np.int32),
                     max_new=2, greedy=bool(i % 2)) for i, p in enumerate(plan)]
    # widths only a long decode reaches
    top = max(lengths)
    for n_new in range(2, max_out + 1):
        b = bucket(top + n_new - 1)
        if b not in seen_b:
            seen_b.add(b)
            out.append(G.Request(index=-1000 - n_new,
                                 prompt=rng.integers(0, vocab, top,
                                                     dtype=np.int32),
                                 max_new=n_new, greedy=False))
    ramp = []
    n = eng["max_slots"]
    if n > 1:
        p = min(lengths)
        # all n run together, then finish one every other step: every
        # count of decoding rows from n down to 1 (and up, on the way in)
        ramp = [G.Request(index=-5000 - i,
                          prompt=rng.integers(0, vocab, p, dtype=np.int32),
                          max_new=3 * n + 2 * i, greedy=bool(i % 2))
                for i in range(n)]
    return out, ramp


def warm(loop, mix, chunk: int, vocab: int):
    """Serve the warm-up requests: one at a time, then the ramp together."""
    single, ramp = warm_requests(mix, chunk, vocab)
    for r in single:
        loop.submit(r)
        loop.drain(float("inf"))
    for r in ramp:
        loop.submit(r)
    loop.drain(float("inf"))
    jax.effects_barrier()
