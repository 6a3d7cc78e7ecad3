"""The packed expert store is built in bounded slabs
(``core/expert_pool.build_store``): each expert matrix stack is quantized
a slab of matrices at a time, and the result is bitwise the store one
HQQ call on the whole stack gives.  With a telemetry attached, each slab
is an ``offload.build`` span and the ``offload`` namespace reports the
store's size and build time."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import OffloadSpec
from repro.core import expert_pool as EP
from repro.core.offload_engine import OffloadEngine
from repro.models import transformer as T
from repro.obs import Telemetry
from repro.quant import hqq
from repro.serving.engine import ContinuousEngine


def _experts(cfg, seed):
    """A parameter tree holding only the expert stacks, bfloat16 as the
    benchmark's weights are."""
    P, E, D, F = cfg.n_periods, cfg.moe.num_experts, cfg.d_model, cfg.d_ff
    key = jax.random.key(seed)
    shapes = {"w_gate": (P, E, D, F), "w_up": (P, E, D, F),
              "w_down": (P, E, F, D)}
    ex = {n: (0.05 * jax.random.normal(jax.random.fold_in(key, i), s)
              ).astype(jnp.bfloat16)
          for i, (n, s) in enumerate(shapes.items())}
    return {"stack": [{"moe": {"experts": ex}}]}


def _one_call(leaf, bits):
    """The store's codes of one stack as one HQQ call on all of it."""
    qt = hqq.quantize(leaf.reshape(-1, *leaf.shape[-2:]), bits)
    lead = leaf.shape[:2]
    r = lambda a: a.reshape(lead + a.shape[1:])
    meta = {k: r(v) for k, v in qt.meta.items()}
    return r(qt.packed), r(qt.scale), r(qt.zero), meta


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


# (registry config, its cut, expert bits, matrices per slab)
CASES = {
    # Mixtral-shaped: 8 experts top-2, 2-bit at group 16; 4 a slab
    # splits each layer's 8 experts in two
    "mixtral": ("mixtral-offload", dict(n_layers=3, d_model=64, d_ff=96),
                2, 4),
    # Granite-shaped: 32 experts, 3-bit at group 64, two slabs a layer
    "granite": ("granite-moe-1b-a400m",
                dict(n_layers=2, d_model=128, d_ff=64), 3, 16),
    # 5 a slab: slabs straddle layers and the last is short (24 matrices
    # a stack = 4 * 5 + 4)
    "uneven": ("mixtral-offload", dict(n_layers=3, d_model=64, d_ff=96),
               2, 5),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_slabbed_store_is_bitwise_one_call(case, monkeypatch):
    name, cut, bits, per = CASES[case]
    cfg = get_config(name).replace(**cut)
    spec = OffloadSpec(cache_size=2, num_speculative=2, expert_bits=bits)
    params = _experts(cfg, seed=sum(map(ord, case)))
    D, F = cfg.d_model, cfg.d_ff
    monkeypatch.setattr(EP, "SLAB_F32_BYTES", per * D * F * 4)
    tel = Telemetry(timing=True, trace=True)
    store = EP.build_store(params, cfg, spec, tel)
    n_mats = cfg.n_periods * cfg.moe.num_experts
    spans = [e for e in tel.tracer.events if e.get("name") ==
             "offload.build"]
    assert len(spans) == 3 * math.ceil(n_mats / per) > 3
    for mat, qt in zip(EP.EXPERT_MATS, store):
        want = _one_call(params["stack"][0]["moe"]["experts"][mat], bits)
        assert qt.shape == (cfg.n_periods, cfg.moe.num_experts) + \
            params["stack"][0]["moe"]["experts"][mat].shape[2:]
        assert (qt.bits, qt.group_size) == (
            bits, hqq.PAPER_SCHEMES[bits]["group_size"])
        for got, ref in zip((qt.packed, qt.scale, qt.zero), want[:3]):
            assert _same_bits(got, ref), mat
        assert set(qt.meta) == set(want[3])
        for k in want[3]:
            assert _same_bits(qt.meta[k], want[3][k]), (mat, k)
        # every matrix is in exactly one slab of its stack
        mine = [e["args"] for e in spans if e["args"]["matrix"] == mat]
        assert sum(a["experts"] for a in mine) == n_mats
        assert sum(a["bytes"] for a in mine) == hqq.nbytes(qt)
        starts = np.cumsum([0] + [a["experts"] for a in mine[:-1]])
        assert [a["layer"] for a in mine] == \
            [int(s) // cfg.moe.num_experts for s in starts]


def test_store_under_the_bound_is_one_slab():
    """At the module's own bound a tiny stack is one slab a matrix."""
    cfg = get_config("mixtral-offload").replace(n_layers=2, d_model=64,
                                                d_ff=96)
    spec = OffloadSpec(cache_size=2, num_speculative=2, expert_bits=2)
    tel = Telemetry(timing=True, trace=True)
    EP.build_store(_experts(cfg, 7), cfg, spec, tel)
    spans = [e["args"] for e in tel.tracer.events
             if e.get("name") == "offload.build"]
    assert [a["experts"] for a in spans] == [16, 16, 16]
    assert [a["matrix"] for a in spans] == list(EP.EXPERT_MATS)


def test_build_spans_and_store_counters(tiny_moe_cfg, tiny_moe_params):
    """An offload engine with a telemetry traces its build, slab by slab,
    and both it and the serving engine over it report the store's packed
    bytes and the build's seconds; without one the counters are there
    and no span is made."""
    cfg, params = tiny_moe_cfg, tiny_moe_params
    spec = OffloadSpec(cache_size=4, num_speculative=2, expert_bits=3,
                       attn_bits=4)
    tel = Telemetry(timing=True, trace=True)
    off = OffloadEngine(params, cfg, spec, quantized=True, telemetry=tel)
    spans = [e for e in tel.tracer.events if e.get("name") ==
             "offload.build"]
    assert len(spans) >= 3
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in spans)
    assert {e["args"]["matrix"] for e in spans} == set(EP.EXPERT_MATS)
    assert sum(e["args"]["bytes"] for e in spans) == off.store.nbytes()
    m = off.metrics()["offload"]
    assert m["store_bytes"] == off.store.nbytes() > 0
    assert m["build_s"] > 0
    # the build's spans together take no longer than the build
    assert sum(e["dur"] for e in spans) / 1e6 <= m["build_s"]
    cont = ContinuousEngine(off.params, cfg, max_slots=1, slot_len=32,
                            offload=off, kv_page=8)
    cm = cont.metrics()["offload"]
    assert (cm["store_bytes"], cm["build_s"]) == (m["store_bytes"],
                                                  m["build_s"])
    quiet = OffloadEngine(params, cfg, spec, quantized=True)
    assert quiet.metrics()["offload"]["store_bytes"] == m["store_bytes"]
    plain = OffloadEngine(params, cfg, spec)  # accounting: nothing built
    pm = plain.metrics()["offload"]
    assert (pm["store_bytes"], pm["build_s"]) == (0, 0.0)


def test_store_serves_the_same_tokens_slabbed(monkeypatch):
    """A packed engine over a store built in many slabs decodes the same
    tokens as one over a one-slab store."""
    cfg = get_config("tiny-moe")
    params = T.init_model(jax.random.key(3), cfg)
    spec = OffloadSpec(cache_size=2, num_speculative=2, expert_bits=2,
                       attn_bits=4)
    prompt = np.arange(1, 9, dtype=np.int32)[None]
    one = OffloadEngine(params, cfg, spec, quantized=True)
    toks_one, _ = one.generate(prompt, 4)
    D, F = cfg.d_model, cfg.d_ff
    monkeypatch.setattr(EP, "SLAB_F32_BYTES", 3 * D * F * 4)
    many = OffloadEngine(params, cfg, spec, quantized=True)
    for a, b in zip(jax.tree.leaves(one.store), jax.tree.leaves(many.store)):
        assert _same_bits(a, b)
    toks_many, _ = many.generate(prompt, 4)
    assert np.array_equal(toks_one, toks_many)
