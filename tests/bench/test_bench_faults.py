"""The check that decides ``correct`` fails what it must: the control
(the reference at 8-bit float activations in the program's place) and each
fault a serving cell can have, planted in the timed path of a tiny run
on the CPU."""
import jax.numpy as jnp
import pytest

import tiny_cell

CELL = "tiny-moe.l2.tiny_b1"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cell.make_root(tmp_path_factory.mktemp("bench"))


def test_control_reads_over_the_limit(root):
    """The control in the program's place, judged by the run's own
    decision, is not correct; the program on the same requests is."""
    out = tiny_cell.run(root, CELL, controls=("fp8",))
    assert out["correct"] is True
    ctl = out["controls"]["fp8"]
    assert ctl["correct"] is False
    assert ctl["check"]["logit_err"]["value"] > \
        tiny_cell.LIMITS["logit_err"]["limit"]


def _token_altered(cont, off):
    """Every sampled token replaced by its neighbour id."""
    orig = cont._sample_rows
    V = cont.cfg.vocab_size
    cont._sample_rows = lambda logits, reqs: (orig(logits, reqs) + 1) % V


def _state_unchanged(cont, off):
    """A decode step that hands back the state it was given."""
    ex = off._exec
    orig = ex.decode

    def decode(state, tokens, pstate=None, active=None, **kw):
        logits, _, pstate, ids = orig(state, tokens, pstate, active, **kw)
        return logits, state, pstate, ids
    ex.decode = decode


def _half_experts(monkeypatch):
    """Half of each token's routed experts left out, the weights
    renormalised over the rest."""
    from repro.models import moe as M
    from repro.models import transformer as T
    orig = M.route_topk

    def route(p, spec, x2d):
        w, ids, probs = orig(p, spec, x2d)
        keep = max(1, w.shape[-1] // 2)
        w = jnp.where(jnp.arange(w.shape[-1]) < keep, w, 0.0)
        return w / w.sum(-1, keepdims=True), ids, probs
    T.cached_jit_clear()
    monkeypatch.setattr(M, "route_topk", route)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_experts"])
def test_fault_reads_incorrect(root, fault, monkeypatch):
    from repro.models import transformer as T
    hooks = None
    if fault == "token_altered":
        hooks = _token_altered
    elif fault == "state_unchanged":
        hooks = _state_unchanged
    else:
        _half_experts(monkeypatch)
    try:
        out = tiny_cell.run(root, CELL, hooks=hooks)
    finally:
        monkeypatch.undo()
        T.cached_jit_clear()
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["check"].values())
