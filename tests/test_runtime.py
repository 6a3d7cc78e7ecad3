"""Unified step-plan runtime (DESIGN.md §8): chunked prefill matches
whole-prompt prefill on every plane (to float32 reassociation), the
token-budget policy's scheduling invariants hold on random traces
(property-based when ``hypothesis`` is installed, with a seeded stdlib
fallback that ALWAYS runs), engines share one compiled block program per
(cfg, plane, mode) per process, and chunked continuous serving matches
unchunked serving token-for-token — including composed with packed
offloading, where the h2d counters must agree exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on hosts w/o the extra
    HAVE_HYPOTHESIS = False

from repro.configs import get_config
from repro.configs.base import OffloadSpec
from repro.core.offload_engine import (OffloadEngine, generate_plain,
                                       quantize_for_offload)
from repro.models import transformer as T
from repro.runtime import Admission, Executor, TokenBudgetPolicy
from repro.serving.engine import ContinuousEngine

import parity


def _prompt(cfg, S, seed=0, B=1):
    rng = np.random.default_rng(seed)
    return rng.integers(1, cfg.vocab_size, (B, S)).astype(np.int32)


# ----------------------------------------------------------------------
# chunked == whole, bitwise, per plane
def test_chunked_prefill_bitwise_plain(tiny_moe_cfg, tiny_moe_params):
    """Acceptance: chunk size never changes the prefill result — logits,
    KV state and positions — beyond float32 reassociation against the
    whole-prompt float32 run: a chunk only changes the number of query
    rows per dispatch (positions and page bookkeeping stay exact)."""
    ex = Executor(tiny_moe_params, tiny_moe_cfg)
    prompt = _prompt(tiny_moe_cfg, 13, seed=3, B=2)  # B=2 lock-step rows
    whole_l, whole_s, _ = ex.prefill(prompt, 32)
    for chunk in (1, 4, 5, 13, 64):
        l, s, _ = ex.prefill(prompt, 32, chunk=chunk)
        parity.assert_close_f32(l[:, -1], whole_l[:, -1],
                                f"chunk={chunk} logits")
        parity.assert_states_close_f32(s, whole_s, f"chunk={chunk}")


@pytest.fixture(scope="module")
def packed_setup(tiny_moe_cfg, tiny_moe_params):
    spec = OffloadSpec(cache_size=2, num_speculative=2, expert_bits=3,
                       attn_bits=4)
    exec_params, _, store = quantize_for_offload(
        tiny_moe_params, tiny_moe_cfg, spec, pack_experts=True)
    qdeq, _ = quantize_for_offload(tiny_moe_params, tiny_moe_cfg, spec)
    return spec, exec_params, store, qdeq


@pytest.mark.parametrize("plane", ["packed_vectorized", "packed_pipelined"])
def test_chunked_prefill_bitwise_packed(tiny_moe_cfg, packed_setup, plane):
    """Same acceptance on the packed planes — chunks stream experts from
    the host store, and the result matches BOTH any other chunking AND
    the float32 plain-plane prefill of the dequantized model, to float32
    reassociation (the fused dequant-matmul contracts in its own
    order)."""
    spec, exec_params, store, qdeq = packed_setup
    ex = Executor(exec_params, tiny_moe_cfg, plane=plane, spec=spec,
                  store=store)
    prompt = _prompt(tiny_moe_cfg, 11, seed=5)
    whole_l, whole_s, _ = ex.prefill(prompt, 24)
    # packed prefill == dequantized-model prefill (plain plane)
    oracle = Executor(qdeq, tiny_moe_cfg)
    ol, os_, _ = oracle.prefill(prompt, 24)
    parity.assert_close_f32(whole_l[:, -1], ol[:, -1], "packed vs oracle")
    parity.assert_states_close_f32(whole_s, os_, "packed vs oracle")
    for chunk in (3, 11):
        l, s, _ = ex.prefill(prompt, 24, chunk=chunk)
        parity.assert_close_f32(l[:, -1], ol[:, -1],
                                f"packed chunk={chunk} logits")
        parity.assert_states_close_f32(s, os_, f"packed chunk={chunk}")


def test_recurrent_stacks_prefill_chunked_bitwise():
    """Recurrent mixers run the SAME chunked prefill program as
    attention stacks (DESIGN.md §12): the chunk forms compose their
    carries exactly, so whole-prompt prefill and any chunking of it
    agree on every state plane to float32 reassociation (the chunk's
    matmuls change row count) — the chunkwise==recurrent guarantee of
    tests/test_recurrent.py lifted to the executor."""
    cfg = get_config("recurrentgemma-9b").reduced()
    params = T.init_model(jax.random.key(2), cfg)
    prompt = _prompt(cfg, 7, seed=2)
    ex = Executor(params, cfg)
    whole_l, whole_s, _ = ex.prefill(prompt, 16)
    for chunk in (4, 7):  # 7 -> 4+3 and whole
        l, s, _ = ex.prefill(prompt, 16, chunk=chunk)
        parity.assert_close_f32(l[:, -1], whole_l[:, -1],
                                f"chunk={chunk} logits")
        parity.assert_states_close_f32(s, whole_s, f"chunk={chunk}")
    # ... and generate_plain is prefill-chunk invariant at token level
    out = generate_plain(params, cfg, prompt, 5)
    assert out.shape == (1, 5)
    out_c = generate_plain(params, cfg, prompt, 5, prefill_chunk=4)
    assert (out == out_c).all()


def test_generate_plain_prefill_chunk_invariant(tiny_moe_cfg,
                                                tiny_moe_params):
    prompt = _prompt(tiny_moe_cfg, 9, seed=7)
    a = generate_plain(tiny_moe_params, tiny_moe_cfg, prompt, 10)
    b = generate_plain(tiny_moe_params, tiny_moe_cfg, prompt, 10,
                       prefill_chunk=2)
    assert (a == b).all()


# ----------------------------------------------------------------------
# continuous serving: chunked admission == unchunked, token for token
def test_continuous_chunked_matches_unchunked(tiny_moe_cfg,
                                              tiny_moe_params):
    """Acceptance: with --prefill-chunk the engine emits, per request,
    bitwise the tokens of unchunked admission under greedy decoding —
    while long prompts no longer monopolise whole steps."""
    cfg, params = tiny_moe_cfg, tiny_moe_params
    prompts = parity.make_prompts(cfg, (21, 5, 17, 4, 12), seed=17)
    max_news = [6, 9, 4, 8, 5]

    base, _ = parity.run_continuous(params, cfg, prompts, max_news)
    for chunk in (4, 7):
        toks, eng = parity.run_continuous(params, cfg, prompts, max_news,
                                          prefill_chunk=chunk)
        parity.assert_tokens_equal(toks, base, f"chunked({chunk})")
        # the budget really bounded every step
        assert eng.budget.token_budget == 2 + chunk
    # and both match the B=1 oracle
    parity.assert_tokens_equal(
        base, parity.oracle_streams(params, cfg, prompts, max_news),
        "unchunked vs oracle")


def test_continuous_offloaded_chunked_matches_and_counters_agree(
        tiny_moe_cfg, tiny_moe_params):
    """Acceptance (packed plane): chunked prefill composed with packed
    offloading matches unchunked token-for-token, and the h2d transfer
    counters are IDENTICAL — prefill chunks stream from the host store
    (zero pool traffic) and, with the pool sized to the expert count,
    decode misses are exactly the cold set either way."""
    cfg, params = tiny_moe_cfg, tiny_moe_params
    spec = OffloadSpec(cache_size=cfg.moe.num_experts, num_speculative=0,
                       expert_bits=3, attn_bits=4)
    off = OffloadEngine(params, cfg, spec, quantized=True)
    prompts = parity.make_prompts(cfg, (19, 5, 14), seed=23)
    max_news = [5, 7, 4]

    base_toks, base_eng = parity.run_continuous(
        None, cfg, prompts, max_news, slot_len=48, offload=off)
    toks, eng = parity.run_continuous(
        None, cfg, prompts, max_news, slot_len=48, offload=off,
        prefill_chunk=5)
    parity.assert_tokens_equal(toks, base_toks, "offloaded chunked")
    base_c, c = (parity.continuous_counters(e) for e in (base_eng, eng))
    assert c == base_c, f"h2d counters changed under chunking: {c} " \
        f"vs {base_c}"
    assert c["offload_demand_loads"] > 0


# ----------------------------------------------------------------------
# token-budget policy invariants (property + seeded fallback)
def _check_budget_policy(chunk_size, token_budget, max_rows, prompt_lens,
                         decode_pattern_seed):
    """Drive the policy over a synthetic admission trace; assert every
    plan respects the budget, chunks are emitted in order and partition
    each prompt, and decode rows are never dropped from a plan."""
    policy = TokenBudgetPolicy(chunk_size=chunk_size,
                               token_budget=token_budget,
                               max_rows=max_rows)
    admissions = [Admission(rid=i, slot=i % max_rows, total=n)
                  for i, n in enumerate(prompt_lens)]
    seen = {a.rid: [] for a in admissions}
    rng = np.random.default_rng(decode_pattern_seed)
    steps = 0
    while admissions:
        n_rows = int(rng.integers(0, max_rows + 1))
        decode_rows = list(range(n_rows))
        plan = policy.plan(decode_rows, admissions)
        # 1. hard budget cap
        assert plan.total_tokens <= token_budget
        # 2. decode rows never starved: every planned step decodes them all
        assert plan.decode_rows == decode_rows
        # 3. progress: the first admission always advances
        assert not admissions or any(c.rid == admissions[0].rid
                                     for c in plan.chunks)
        for c in plan.chunks:
            adm = next(a for a in admissions if a.rid == c.rid)
            # 4. in order, gapless
            assert c.lo == adm.next_lo
            assert c.hi <= adm.total
            assert c.last == (c.hi == adm.total)
            seen[c.rid].append((c.lo, c.hi))
            adm.next_lo = c.hi
        admissions = [a for a in admissions if not a.done]
        steps += 1
        assert steps < 10_000, "policy livelocked"
    # 5. chunks partition each prompt exactly
    for adm_id, chunks in seen.items():
        total = prompt_lens[adm_id]
        assert chunks[0][0] == 0 and chunks[-1][1] == total
        for (a0, a1), (b0, b1) in zip(chunks, chunks[1:]):
            assert a1 == b0


FALLBACK_CASES = [
    (1, 5, 4, (1, 1, 9), 0),
    (4, 8, 4, (13, 2, 7, 31), 1),
    (8, 16, 8, (64, 1, 8, 9, 17), 2),
    (3, 20, 2, (5, 5, 5, 4), 3),
    (16, 18, 2, (100,), 4),
]


def test_budget_policy_invariants_fallback():
    """Seeded stdlib fallback that always runs (property-module guard:
    the scheduling invariants must not vanish with optional deps)."""
    for case in FALLBACK_CASES:
        _check_budget_policy(*case)


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(chunk_size=st.integers(1, 16),
           extra_budget=st.integers(0, 32),
           max_rows=st.integers(1, 8),
           prompt_lens=st.lists(st.integers(1, 80), min_size=1,
                                max_size=6),
           seed=st.integers(0, 2**16))
    def test_budget_policy_invariants_property(chunk_size, extra_budget,
                                               max_rows, prompt_lens,
                                               seed):
        token_budget = chunk_size + max_rows + extra_budget
        _check_budget_policy(chunk_size, token_budget, max_rows,
                             tuple(prompt_lens), seed)


def test_budget_policy_rejects_livelock_budget():
    with pytest.raises(ValueError):
        TokenBudgetPolicy(chunk_size=8, token_budget=8, max_rows=4)
    with pytest.raises(ValueError):
        ContinuousEngine(None, get_config("tiny-moe"), max_slots=2,
                         slot_len=32, token_budget=16)  # no prefill_chunk


# ----------------------------------------------------------------------
# compile-once: shared block programs per (cfg, plane, mode)
def test_executor_block_programs_compile_once(tiny_moe_cfg,
                                              tiny_moe_params,
                                              packed_setup):
    """The runtime refactor's shared block programs build once per
    (cfg, plane, mode) per process: constructing and running a SECOND
    executor/engine of an identical mode adds zero cache builds."""
    cfg, params = tiny_moe_cfg, tiny_moe_params
    spec, exec_params, store, _ = packed_setup
    prompt = _prompt(cfg, 6, seed=9)
    T.cached_jit_clear()  # count only the shapes this test compiles

    def exercise(make):
        ex = make()
        if ex.packed:
            ps = ex.init_pool_state()
            logits, state, ps = ex.prefill(prompt, 12, pstate=ps)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            ex.decode(state, tok, ps)
        else:
            logits, state, _ = ex.prefill(prompt, 12)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            ex.decode(state, tok)

    for make in (
            lambda: Executor(params, cfg),
            lambda: Executor(exec_params, cfg, plane="packed_pipelined",
                             spec=spec, store=store),
            lambda: Executor(exec_params, cfg, plane="packed_vectorized",
                             spec=spec, store=store)):
        exercise(make)  # first pass may build missing programs
        before = T.cached_jit_stats()["builds"]
        exercise(make)  # identical mode: every program must be a hit
        after = T.cached_jit_stats()["builds"]
        assert after == before, \
            f"identical executor mode rebuilt {after - before} programs"
        ex = make()
        if ex.packed:
            # one compiled layer program per kind and chunk width across
            # all layers: the layer's period index rides in traced.  The
            # mixer serves the prompt chunk and (pipelined) decode; the
            # vectorized plane decodes through its block program
            kind, = set(ex.kinds)
            if ex.pipelined:
                assert ex._mixer_blk(kind)._cache_size() == 2
            else:
                assert ex._decode_blk(kind)._cache_size() == 1


def test_cached_jit_stats_and_clear():
    key = ("__test_runtime_probe__",)
    T.cached_jit(key, lambda: object())
    s = T.cached_jit_stats()
    assert key in s["keys"] and s["entries"] >= 1 and s["builds"] >= 1
    T.cached_jit(key, lambda: object())
    assert T.cached_jit_stats()["hits"] >= 1
    T.cached_jit_clear()
    s = T.cached_jit_stats()
    assert s["entries"] == 0 and s["builds"] == 0 and s["hits"] == 0
