"""The packed planes' layer programs read and write each layer's decode
state inside the program (``repro.runtime.executor``): they take the
layer-stacked state tree with the layer's period index traced, donate
the tree, and return it with the layer written back — tail layers pass
their own state.  A step updates its own copy of the state, made once,
so its caller's state stays valid.

Checked on a stack that has every case: two block kinds in the pattern
(MoE and dense MLP), two periods, and a tail layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OffloadSpec
from repro.core.offload_engine import OffloadEngine, quantize_for_offload
from repro.models import transformer as T
from repro.runtime import Executor
from repro.serving.engine import ContinuousEngine
from repro.serving.faults import FaultInjector, FaultSpec
from repro.serving.sampler import SamplerConfig

import parity

# layers: attn+mlp, swa+moe (period 0), attn+mlp, swa+moe (period 1),
# then the tail layer attn+mlp
CFG = get_config("tiny-moe").replace(
    name="tiny-moe-p2t1", n_layers=5, block_pattern=("attn+mlp", "swa+moe"))
SPEC = OffloadSpec(cache_size=2, num_speculative=2, lookahead=1,
                   expert_bits=3, attn_bits=4)
PLANES = ("packed_pipelined", "packed_vectorized", "fault_ladder")


@pytest.fixture(scope="module")
def model():
    assert (CFG.pattern_period, CFG.n_periods, CFG.n_tail_layers) == (2, 2, 1)
    params = T.init_model(jax.random.key(4), CFG)
    exec_params, _, store = quantize_for_offload(params, CFG, SPEC,
                                                 pack_experts=True)
    qdeq, _ = quantize_for_offload(params, CFG, SPEC)
    return params, exec_params, store, qdeq


def _executor(model, plane):
    _, exec_params, store, _ = model
    ex = Executor(exec_params, CFG, spec=SPEC, store=store,
                  plane="packed_pipelined" if plane == "fault_ladder"
                  else plane)
    if plane == "fault_ladder":
        # every fetch and retry fails: each MoE layer degrades to the
        # store-direct path, whose mixer is the same layer program
        ex.set_fault_injector(FaultInjector(
            [FaultSpec(site="expert_fetch", rate=1.0)], seed=0))
    return ex


def _prompt(S, seed):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, (1, S)).astype(np.int32)


@pytest.mark.parametrize("plane", PLANES)
def test_packed_layer_state_matches_plain_plane(model, plane):
    """Prompt chunks, then greedy decode: every step's tokens equal the
    plain plane's on the dequantized weights, and the logits and every
    layer's state — stacked periods and the tail — agree to float32
    reassociation (the fused dequant-matmul contracts in its own
    order).  A layer read from or written to the wrong period moves
    them by their own scale."""
    ex = _executor(model, plane)
    oracle = Executor(model[3], CFG)
    prompt = _prompt(9, seed=1)
    pstate = ex.init_pool_state()
    logits, state, pstate = ex.prefill(prompt, 20, chunk=4, pstate=pstate)
    o_logits, o_state, _ = oracle.prefill(prompt, 20, chunk=4)
    parity.assert_close_f32(logits[:, -1], o_logits[:, -1], "prefill")
    parity.assert_states_close_f32(state, o_state, "prefill state")
    for i in range(6):
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        o_tok = jnp.argmax(o_logits[:, -1], -1)[:, None].astype(jnp.int32)
        assert int(tok[0, 0]) == int(o_tok[0, 0]), f"token {i}"
        logits, state, pstate, _ = ex.decode(state, tok, pstate)
        o_logits, o_state, _, _ = oracle.decode(o_state, o_tok)
        parity.assert_close_f32(logits[:, -1], o_logits[:, -1],
                                f"decode {i}")
    parity.assert_states_close_f32(state, o_state, "decode state")
    if plane == "fault_ladder":
        assert ex.fault_counters["fetch_degraded"] > 0


def _layer_leaves(state):
    return jax.tree.leaves((state["stack"], state["tail"]))


@pytest.mark.parametrize("plane", PLANES[:2])
def test_packed_step_keeps_its_input_and_updates_in_place(model, plane):
    """A packed prompt chunk or decode step leaves its input state valid
    and unchanged — a caller may still read the pre-step state — and
    returns buffers of its own; the layer programs donate the stacked
    tree they are given, so the step's copy is updated in place."""
    ex = _executor(model, plane)
    pstate = ex.init_pool_state()
    state = ex.init_state(1, 16)
    before = [np.asarray(a) for a in _layer_leaves(state)]
    logits, chunked, pstate = ex.prefill_chunk(
        state, jnp.asarray(_prompt(5, 2)), pstate)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    _, decoded, _, _ = ex.decode(chunked, tok, pstate)
    for old, new in ((state, chunked), (chunked, decoded)):
        assert not any(a.is_deleted() for a in _layer_leaves(old))
        ptrs = {a.unsafe_buffer_pointer() for a in _layer_leaves(old)}
        assert not ptrs & {a.unsafe_buffer_pointer()
                           for a in _layer_leaves(new)}
    for a, b in zip(before, _layer_leaves(state)):
        np.testing.assert_array_equal(a, np.asarray(b))
    # layer 1 (swa+moe, period 0): its mixer program consumes the tree
    where, i, per = ex._state_at[1]
    tree = decoded[where][i]
    ex._mixer_blk(ex.kinds[1])(ex._layer_p[1], ex._embed(tok), tree, per,
                               decoded["pos"], None, None)
    assert per is not None and all(a.is_deleted()
                                   for a in jax.tree.leaves(tree))


def _check_engine(params):
    """The benchmark's program at tiny size: packed pipelined offload,
    one slot, paged KV, chunked prefill, sampling at temperature 1."""
    return ContinuousEngine(
        None, CFG, max_slots=1, slot_len=32, eos_id=None, prefill_chunk=4,
        kv_page=8, seed=7,
        offload=OffloadEngine(params, CFG, SPEC, quantized=True),
        sampler=SamplerConfig(kind="categorical", temperature=1.0))


def _serve_greedy(eng, prompt, max_new):
    """Serve one greedy request; returns (tokens, logits rows)."""
    rows = []
    sample_rows = eng._sample_rows

    def keep(logits, reqs):
        rows.append(np.asarray(logits[0]))
        return sample_rows(logits, reqs)
    eng._sample_rows = keep
    req = eng.submit(prompt, max_new, temperature=0.0)
    eng.run(max_steps=200)
    assert req.status == "completed"
    return req.generated, np.stack(rows)


def test_cancel_mid_decode_then_greedy_matches_fresh_engine(model):
    """The benchmark check's own sequence: a sampled request is cancelled
    mid-decode, then one greedy request is served by the same engine.
    Its tokens and logits are those of a fresh engine serving it alone:
    no page, pool slot or layer state of the cancelled request leaks."""
    eng = _check_engine(model[0])
    first = eng.submit(_prompt(11, 3)[0], 12)
    while len(first.generated) < 4:
        eng.step()
    assert eng.cancel(first.rid) and first.status == "cancelled"
    prompt = _prompt(10, 4)[0]
    toks, rows = _serve_greedy(eng, prompt, 8)
    want_toks, want_rows = _serve_greedy(_check_engine(model[0]), prompt, 8)
    assert toks == want_toks
    np.testing.assert_array_equal(rows, want_rows)
