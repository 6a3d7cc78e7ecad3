"""One runtime for the whole config zoo (DESIGN.md §12).

The per-layer-kind state-plane refactor's acceptance bar: every arch
family — recurrent (rg_lru: recurrentgemma), pure-recurrent
(mlstm/slstm: xlstm), encoder-decoder (whisper) and plain dense
(stablelm) — decodes through the SAME ContinuousEngine, and each
request's continuous/chunked greedy stream is BITWISE its own
single-request ``generate_plain`` oracle.

Also pinned here:

* zero-page admission: a pure-recurrent stack under the paged manager
  reserves no pool pages, so admission can never stall on the pool —
  a one-page pool serves any number of xlstm requests;
* chunked prefill ≡ whole prefill on every state plane for recurrent
  stacks, to float32 reassociation (the exact-carry chunk forms of
  tests/test_recurrent.py, lifted through the executor);
* speculative decoding on recurrent stacks: rollback is
  snapshot-and-restore of the pre-round row state (mirroring the paged
  page-table trim), and the emitted streams stay bitwise the plain
  engine's;
* enc-dec admission: ``extras["audio_embeds"]`` is encoded ONCE into
  the read-only shared encoder-KV plane; submitting without it is an
  error, not a hang.

This module is in conftest.PROPERTY_MODULES: a skip here silently
retires the zoo acceptance bar, so CI fails on skips.
"""
import jax
import numpy as np
import pytest

import parity
from repro.configs import get_config
from repro.models import transformer as T
from repro.serving.engine import ContinuousEngine
from repro.serving.kv_manager import PagedKVManager, StateManager

ZOO = ("recurrentgemma-9b", "xlstm-1.3b", "whisper-medium",
       "stablelm-1.6b")

_cache = {}


def _model(name):
    if name not in _cache:
        cfg = get_config(name).reduced()
        _cache[name] = (cfg, T.init_model(jax.random.key(0), cfg))
    return _cache[name]


def _workload(cfg, lens=(5, 9, 13), news=(6, 5, 4), seed=1):
    prompts = parity.make_prompts(cfg, lens, seed=seed)
    extras = parity.make_extras(cfg, len(prompts))
    return prompts, list(news), extras


# ----------------------------------------------------------------------
# the zoo x KV-layout matrix, every cell bitwise vs the B=1 oracle
@pytest.mark.parametrize("variant", ["dense", "dense_chunked", "paged"])
@pytest.mark.parametrize("arch", ZOO)
def test_zoo_continuous_matches_oracle(arch, variant):
    cfg, params = _model(arch)
    kw = dict(parity.CONTINUOUS_KV_VARIANTS[variant])
    if variant == "paged" and not cfg.has_kv_layers:
        # pure-recurrent: exercise the ZERO-page path hard — a pool of
        # one page must serve all requests (none are ever reserved)
        kw["kv_pages_total"] = 1
    prompts, max_news, extras = _workload(cfg)
    want = parity.oracle_streams(params, cfg, prompts, max_news, extras)
    got, eng = parity.run_continuous(params, cfg, prompts, max_news,
                                     extras=extras, **kw)
    parity.assert_tokens_equal(got, want, f"{arch}/{variant}")
    assert eng.sched.joins == len(prompts) > eng.max_slots  # churn happened


# ----------------------------------------------------------------------
def test_zero_page_admission_not_refused():
    """Regression (the pre-§12 engine reserved prompt+max_new pages for
    EVERY arch): a pure-recurrent request bigger than the whole page
    pool must still admit — it needs zero pages."""
    cfg, params = _model("xlstm-1.3b")
    eng = ContinuousEngine(params, cfg, max_slots=2, slot_len=64,
                           eos_id=None, kv_page=16, kv_pages_total=1)
    need = 40  # prompt + max_new >> pool capacity (16 positions)
    assert eng.kv.can_admit(need)
    req = eng.submit(parity.make_prompts(cfg, [30])[0], 10)
    eng.run(max_steps=100)
    assert req.state == "finished" and len(req.generated) == 10
    assert eng.kv.pool.owned.get(req.slot, None) in (None, [])


def test_statemanager_facade_dispatch():
    cfg, _ = _model("xlstm-1.3b")
    dense = StateManager.create(cfg, 2, 32)
    paged = StateManager.create(cfg, 2, 32, kv_page=8)
    assert not isinstance(dense, PagedKVManager)
    assert isinstance(paged, PagedKVManager) and not paged.has_kv
    with pytest.raises(ValueError, match="kv_page"):
        StateManager.create(cfg, 2, 32, kv_pages_total=4)


# ----------------------------------------------------------------------
def test_recurrent_chunked_prefill_bitwise_every_plane():
    """Chunked ≡ whole prefill on every carry, to float32
    reassociation against the whole-prompt float32 run (a chunk changes
    its matmuls' row count) — the chunkwise==recurrent oracle of
    tests/test_recurrent.py driven through the executor for both
    recurrent families."""
    for arch, chunks in (("recurrentgemma-9b", (3, 4)),
                         ("xlstm-1.3b", (1, 3, 4))):
        cfg, params = _model(arch)
        from repro.runtime import Executor
        ex = Executor(params, cfg)
        prompt = parity.make_prompts(cfg, [11], seed=4)[0][None]
        whole_l, whole_s, _ = ex.prefill(prompt, 24)
        for c in chunks:  # 11 -> 3,3,3,2 / 4,4,3 / 1 x 11
            l, s, _ = ex.prefill(prompt, 24, chunk=c)
            parity.assert_close_f32(l[:, -1], whole_l[:, -1],
                                    f"{arch} chunk={c} logits")
            parity.assert_states_close_f32(s, whole_s,
                                           f"{arch} chunk={c}")


# ----------------------------------------------------------------------
# speculative decoding over recurrent state: snapshot-and-restore
def test_speculative_recurrent_bitwise():
    dcfg = get_config("tiny-draft")
    dparams = T.init_model(jax.random.key(1), dcfg)
    for arch, lens, news in (("xlstm-1.3b", (5, 9, 13), (7, 5, 6)),
                             ("recurrentgemma-9b", (5, 7, 9), (4, 3, 4))):
        cfg, params = _model(arch)
        assert dcfg.vocab_size == cfg.vocab_size
        prompts = parity.make_prompts(cfg, lens)
        want = parity.oracle_streams(params, cfg, prompts, list(news))
        got, eng = parity.run_continuous(
            params, cfg, prompts, list(news), draft_params=dparams,
            draft_cfg=dcfg, num_draft_tokens=2)
        parity.assert_tokens_equal(got, want, f"{arch}/speculative")
        assert eng.obs.snapshot()["spec"]["rounds"] > 0  # spec path ran


def test_speculative_recurrent_rejects_paged():
    cfg, params = _model("xlstm-1.3b")
    dcfg = get_config("tiny-draft")
    dparams = T.init_model(jax.random.key(1), dcfg)
    with pytest.raises(ValueError, match="snapshot"):
        ContinuousEngine(params, cfg, max_slots=2, slot_len=64,
                         kv_page=16, draft_params=dparams,
                         draft_cfg=dcfg, num_draft_tokens=2)


# ----------------------------------------------------------------------
def test_encdec_submit_requires_audio():
    cfg, params = _model("whisper-medium")
    eng = ContinuousEngine(params, cfg, max_slots=2, slot_len=64,
                           eos_id=None)
    with pytest.raises(ValueError, match="audio_embeds"):
        eng.submit(np.arange(1, 6, dtype=np.int32), 4)
    with pytest.raises(ValueError, match="audio_embeds"):
        eng.submit(np.arange(1, 6, dtype=np.int32), 4,
                   extras={"audio_embeds": np.zeros(
                       (cfg.encoder_seq + 1, cfg.d_model), np.float32)})


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled():
    yield
    _cache.clear()
    T.cached_jit_clear()
    jax.clear_caches()
