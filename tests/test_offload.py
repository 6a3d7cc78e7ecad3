"""Offload engine invariants (the paper's system, end to end):
accounting mode is pure scheduling; packed mode executes on HQQ-packed
weights through the device buffer pool and stays bit-identical to the
dequantized model (DESIGN.md §6)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OffloadSpec
from repro.core import expert_pool as EP
from repro.core.offload_engine import (OffloadEngine, generate_plain,
                                       quantize_for_offload)
from repro.models import transformer as T
from repro.quant import hqq

import parity


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tiny-moe")
    params = T.init_model(jax.random.key(0), cfg)
    prompt = np.array([[72, 101, 108, 108, 111, 32, 119]], np.int32)
    return cfg, params, prompt


# session-scoped shared engines/stores (tier-1 runtime guard): HQQ
# quantization + engine construction cost seconds each; every test that
# only *reads* generation behaviour shares one instance
@pytest.fixture(scope="module")
def qdeq(setup):
    cfg, params, _ = setup
    return quantize_for_offload(params, cfg, SPEC)[0]


@pytest.fixture(scope="module")
def packed_eng(setup):
    cfg, params, _ = setup
    return OffloadEngine(params, cfg, SPEC, quantized=True)


def test_offloading_is_pure_scheduling(setup):
    """Offloaded generation must be bit-identical to plain decode."""
    cfg, params, prompt = setup
    plain = generate_plain(params, cfg, prompt, 16)
    eng = OffloadEngine(params, cfg)
    off, stats = eng.generate(prompt, 16)
    assert (plain == off).all()
    assert stats.accesses == (16 - 1) * cfg.moe_layer_count * cfg.moe.top_k


def test_bigger_cache_fewer_demand_loads(setup):
    cfg, params, prompt = setup
    loads = {}
    for k in (1, 2, 4, 8):
        spec = OffloadSpec(cache_size=k, num_speculative=0)
        eng = OffloadEngine(params, cfg, spec)
        _, stats = eng.generate(prompt, 24)
        loads[k] = stats.demand_loads
    assert loads[1] >= loads[2] >= loads[4] >= loads[8]
    assert loads[8] <= cfg.moe_layer_count * cfg.moe.num_experts  # warmup only


def test_speculation_reduces_blocking_loads(setup):
    cfg, params, prompt = setup
    base = OffloadEngine(params, cfg, OffloadSpec(cache_size=2,
                                                  num_speculative=0))
    spec = OffloadEngine(params, cfg, OffloadSpec(cache_size=2,
                                                  num_speculative=2))
    _, s0 = base.generate(prompt, 24)
    _, s1 = spec.generate(prompt, 24)
    assert s1.demand_loads < s0.demand_loads
    assert s1.spec_hits > 0


def test_quantized_sizes_and_quality(setup, packed_eng):
    cfg, params, prompt = setup
    sizes = packed_eng.size_report
    assert sizes["experts"] > 0 and sizes["attn"] > 0
    # experts dominate and compress well below fp16
    fp16_experts = sum(
        l.size * 2 for l in jax.tree.leaves(
            [params["stack"][0]["moe"]["experts"]]))
    assert sizes["experts"] < 0.30 * fp16_experts  # ~3.5/16 bits
    # quantized model still generates (finite logits, valid tokens)
    out, stats = packed_eng.generate(prompt, 8)
    assert out.shape == (1, 8)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()


# ----------------------------------------------------------------------
# packed execution (DESIGN.md §6)
SPEC = OffloadSpec(cache_size=2, num_speculative=2, lookahead=1,
                   expert_bits=3, attn_bits=4)


def test_packed_generate_bit_identical_to_dequantized(setup, qdeq,
                                                      packed_eng):
    """Acceptance: quantized (packed) generation is bit-identical to
    decoding the dequantized model, while experts stay HQQ-packed —
    the only dense expert weights ever built are per-slot dequants."""
    cfg, params, prompt = setup
    oracle = parity.oracle_streams(qdeq, cfg, [prompt[0]], [12])[0]
    out, stats = parity.run_offload_generate(packed_eng, prompt, 12)
    parity.assert_tokens_equal(out, oracle, "packed vs dequantized oracle")
    # real traffic happened and the LRU worked
    assert stats.demand_loads > 0 and stats.hits > 0
    assert stats.n_tokens == 11
    # no dense expert stack exists in the executable params
    for i in range(cfg.pattern_period):
        ex = packed_eng.params["stack"][i]["moe"]["experts"]
        assert all(leaf.size == 0 for leaf in jax.tree.leaves(ex))


def test_packed_einsum_mode_matches_fused(setup, packed_eng):
    """fused=False (per-slot dequant into the gather einsums) and
    fused=True (kernels/ops.dequant_matmul_batched) agree bitwise."""
    cfg, params, prompt = setup
    b = OffloadEngine(params, cfg, SPEC, quantized=True, fused=False)
    out_a, _ = packed_eng.generate(prompt, 10)
    out_b, _ = b.generate(prompt, 10)
    assert (out_a == out_b).all()


def test_packed_pipelined_matches_synchronous_unrolled(setup, packed_eng):
    """Tentpole invariant (DESIGN.md §7): the vectorized overlap-pipelined
    stream produces bitwise the tokens AND the transfer counters of the
    PR-2 synchronous per-(token, k) data plane."""
    cfg, params, prompt = setup
    base = OffloadEngine(params, cfg, SPEC, quantized=True,
                         pipelined=False, vectorized=False)
    out_b, sb = parity.run_offload_generate(base, prompt, 8)
    out_p, sp = parity.run_offload_generate(packed_eng, prompt, 8)
    parity.assert_tokens_equal(out_p, out_b, "pipelined vs sync")
    assert parity.offload_counters(sp) == parity.offload_counters(sb)


def test_generate_rng_none_samples(setup, qdeq, packed_eng):
    """Regression: ``generate(greedy=False)`` without an rng used to
    crash inside ``jax.random.split``; both engine modes must fall back
    to a seeded default key."""
    cfg, params, prompt = setup
    out_p, _ = packed_eng.generate(prompt, 4, greedy=False)
    acct = OffloadEngine(qdeq, cfg, SPEC, quantized=False)
    out_a, _ = acct.generate(prompt, 4, greedy=False)
    for out in (out_p, out_a):
        assert out.shape == (1, 4)
        assert (out >= 0).all() and (out < cfg.vocab_size).all()


def test_usage_tracker_overlap_normalizes_by_scored_layers():
    """Regression: ``ExpertUsageTracker.overlap`` summed only the first
    ``n_layers`` prediction lists but divided by the TOTAL supplied,
    deflating scores for candidates with surplus layers."""
    from repro.core.offload_engine import ExpertUsageTracker

    tr = ExpertUsageTracker(n_layers=2, n_experts=4)
    tr.update([np.array([[0, 1]]), np.array([[2, 3]])])
    pred = [np.array([[0, 1]]), np.array([[2, 3]])]
    base = tr.overlap(pred)
    assert base > 0
    # extra prediction layers beyond the tracker are not scored — they
    # must not dilute the score either
    assert tr.overlap(pred + pred) == pytest.approx(base)


def test_device_buffer_pool_holds_cache_size_slots(setup):
    """Acceptance: the device buffer pool holds exactly ``cache_size``
    expert slots per MoE layer (plus ``num_speculative`` staging
    buffers); only the host store holds all E experts."""
    cfg, params, prompt = setup
    spec = OffloadSpec(cache_size=3, num_speculative=2, expert_bits=3,
                       attn_bits=4)
    eng = OffloadEngine(params, cfg, spec, quantized=True)
    _, _ = eng.generate(prompt, 4)
    ps = eng._last_pool_state
    L = eng.n_moe_layers
    for qt in ps.pool:
        assert qt.shape[:2] == (L, spec.cache_size)
    for qt in ps.staging:
        assert qt.shape[:2] == (L, spec.num_speculative)
    for qt in eng.store:
        assert qt.shape[:2] == (L, cfg.moe.num_experts)
    assert ps.lru.cache_ids.shape == (L, spec.cache_size)


def test_packed_stats_are_measured_copies(setup, packed_eng):
    """expert_bytes equals the real packed size of one expert's slot
    (packed codes + scale/zero + meta), not a cost-model estimate."""
    one = packed_eng.store.slice(0, 0)
    assert packed_eng.expert_bytes == one.nbytes()
    assert packed_eng.size_report["experts"] == packed_eng.store.nbytes()


def test_packed_counters_match_accounting_replay(setup, qdeq, packed_eng):
    """The packed engine's measured hit/load counters equal the
    accounting engine's PyLRU replay over the (bitwise-identical)
    dequantized model — same routing, same cache policy, two
    implementations."""
    cfg, params, prompt = setup
    acct = OffloadEngine(qdeq, cfg, SPEC, quantized=False)
    out_p, sp = parity.run_offload_generate(packed_eng, prompt, 12)
    out_a, sa = parity.run_offload_generate(acct, prompt, 12)
    parity.assert_tokens_equal(out_p, out_a, "packed vs accounting")
    assert parity.offload_counters(sp) == parity.offload_counters(sa)


def test_pool_slots_agree_with_lru_state(setup, packed_eng):
    """Data-plane/state-machine coherence: after generation, each LRU
    slot's packed bytes are exactly the host store's bytes for the
    expert the state machine says lives there."""
    cfg, params, prompt = setup
    eng = packed_eng
    eng.generate(prompt, 10)
    ps = eng._last_pool_state
    ids = np.asarray(ps.lru.cache_ids)  # (L, k)
    for l in range(eng.n_moe_layers):
        for s in range(SPEC.cache_size):
            e = int(ids[l, s])
            if e < 0:
                continue
            slot = ps.pool.slice(l, s)
            ref = eng.store.slice(l, e)
            for qs, qr in zip(slot, ref):
                assert (np.asarray(qs.packed) == np.asarray(qr.packed)).all()
                assert (np.asarray(qs.scale) == np.asarray(qr.scale)).all()


def _packed_moe_setup(bits=3):
    """Store + cold pool for moe-level packed-path unit tests."""
    cfg = get_config("tiny-moe")  # dims divide every scheme's group size
    params = T.init_model(jax.random.key(20), cfg)
    spec = OffloadSpec(cache_size=2, num_speculative=2, expert_bits=bits,
                       attn_bits=4)
    store = EP.build_store(params, cfg, spec)
    pstate = EP.init_pool_state(store, spec)
    return cfg, params, spec, store, pstate


@pytest.mark.parametrize("fused", [True, False])
def test_moe_packed_matches_gather_on_dequantized_stack(fused):
    """moe_apply_packed == moe_apply_gather over the dequantized expert
    stack — per-slot dequant commutes with stacking (the packed-execution
    parity invariant at the single-layer level).  ``fused=False`` runs
    the gather path's very einsums, so it stays bitwise; the fused
    batched dequant-matmul contracts in its own order and matches to
    float32 reassociation."""
    import jax.numpy as jnp

    from repro.core.trace import stacked_routers
    from repro.models import moe as M

    cfg, params, spec, store, pstate = _packed_moe_setup()
    l = 2
    p_moe = T.layer_params(params, cfg, l)["moe"]
    ex_deq = {name: hqq.dequantize(hqq.slice_leading(qt, l),
                                   jnp.dtype(cfg.dtype))
              for name, qt in zip(("w_gate", "w_up", "w_down"), store)}
    x = jax.random.normal(jax.random.key(21), (1, cfg.d_model))
    y_ref, route_ref = M.moe_apply_gather(
        {"router": p_moe["router"], "experts": ex_deq}, cfg, x)
    routers = jnp.asarray(stacked_routers(params, cfg))
    y, route, pstate2 = M.moe_apply_packed(
        p_moe, cfg, x, store, pstate, jnp.asarray(l), routers,
        lookahead=spec.lookahead, n_spec=spec.num_speculative, fused=fused)
    assert (np.asarray(route["ids"]) == np.asarray(route_ref["ids"])).all()
    if fused:
        parity.assert_close_f32(y, y_ref, "fused packed vs gather")
    else:
        assert (np.asarray(y) == np.asarray(y_ref)).all()
    # cold pool -> both routed experts were demand loads
    counts = np.asarray(pstate2.counts)
    assert counts[2] == cfg.moe.top_k
    # speculation staged into layer l+1's buffers
    assert counts[3] > 0
    assert (np.asarray(pstate2.lru.spec_ids[l + 1]) >= 0).all()


def test_moe_packed_prefill_ffn_matches_dense_dispatch():
    """Expert-streaming dispatch (the packed prefill path) == dispatch
    over the dequantized stack, bitwise."""
    import jax.numpy as jnp

    from repro.models import moe as M

    cfg, params, spec, store, pstate = _packed_moe_setup()
    l = 0
    p_moe = T.layer_params(params, cfg, l)["moe"]
    ex_deq = {name: hqq.dequantize(hqq.slice_leading(qt, l),
                                   jnp.dtype(cfg.dtype))
              for name, qt in zip(("w_gate", "w_up", "w_down"), store)}
    x = jax.random.normal(jax.random.key(22), (24, cfg.d_model))
    y_ref, _ = M.moe_apply_dispatch(
        {"router": p_moe["router"], "experts": ex_deq}, cfg, x)
    y, _ = M.moe_apply_dispatch(
        p_moe, cfg, x,
        expert_ffn_fn=M.packed_expert_ffn(store, jnp.asarray(l), cfg))
    assert (np.asarray(y) == np.asarray(y_ref)).all()


def test_throughput_estimates_ordering(setup, packed_eng):
    """Cost model must reproduce Table 2's hardware ordering."""
    cfg, params, prompt = setup
    eng = packed_eng  # default spec == SPEC; shared engine (runtime guard)
    _, stats = eng.generate(prompt, 16)
    mixtral = get_config("mixtral-8x7b")  # project to paper scale
    from repro.core import cost_model as C
    tps = {hw: C.tokens_per_second(mixtral, C.HARDWARE[hw],
                                   stats.per_token(), 3)
           for hw in ("t4", "3060", "3080m", "a100")}
    assert tps["a100"] > tps["3080m"] > tps["3060"] > tps["t4"]
    # naive offloading is strictly worse than the cached policy
    naive = C.tokens_per_second(mixtral, C.HARDWARE["t4"],
                                C.TokenStats(0, 0, 0, 0), 3, naive=True)
    assert naive < tps["t4"]
