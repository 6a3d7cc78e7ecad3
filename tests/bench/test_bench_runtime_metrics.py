"""The readers that split the program's ``dispatch`` phase
(``bench/dispatch_split.py``, ``bench/metrics/runtime.*``): on hand-built
telemetry, and on the spans of a tiny offloaded engine run."""
import numpy as np
import pytest

import tiny_cell
import dispatch_split as D
import run as R


def _x(name, tid, ts, dur, **args):
    ev = {"ph": "X", "name": name, "pid": 0, "tid": tid, "ts": float(ts),
          "dur": float(dur)}
    if args:
        ev["args"] = args
    return ev


def _step(i, t0, sites, *, decode_rows=1, programs=True):
    """A decode step at ``t0`` us: plan 5, chunk 5, dispatch 80, sample 5,
    host 5; ``sites`` (name, start, dur) are offsets into dispatch."""
    args = {"decode_rows": decode_rows, "chunks": 0}
    if programs:
        args.update(programs=sum(n.startswith("exec.") and n != "exec.state"
                                 for n, _, _ in sites), fetches=2)
    evs = [_x(f"step {i}", 0, t0, 100, **args),
           _x("plan", 0, t0, 5), _x("chunk", 0, t0 + 5, 5),
           _x("dispatch", 0, t0 + 10, 80), _x("sample", 0, t0 + 90, 5),
           _x("host", 0, t0 + 95, 5)]
    evs += [_x(n, 1, t0 + 10 + s, d) for n, s, d in sites]
    return evs


# launch 3+10+10+5+5 = 33 us, state 5+10 = 15, route fetch 15, self 17
SITES_A = [("exec.embed", 2, 3), ("exec.state", 5, 5), ("exec.mixer", 10, 10),
           ("exec.moe", 20, 10), ("exec.stage", 30, 5), ("exec.state", 35, 10),
           ("exec.head", 50, 5), ("engine.route_fetch", 60, 15)]
# launch 30, state 20, route fetch 10, self 20
SITES_B = [("exec.embed", 0, 10), ("exec.state", 10, 20),
           ("exec.block", 30, 20), ("engine.route_fetch", 60, 10)]
# launch 40, state 30, route fetch 5, self 5
SITES_C = [("exec.embed", 0, 20), ("exec.state", 20, 30),
           ("exec.head", 50, 20), ("engine.route_fetch", 75, 5)]


def _run(events, window=(0.0, 1e-3)):
    return {"telemetry": events, "telemetry_t0_ns": 0, "window": window}


def _three_steps(**kw):
    return _run(_step(0, 0, SITES_A, **kw) + _step(1, 200, SITES_B, **kw)
                + _step(2, 400, SITES_C, **kw)
                # a fourth step ends after the window (1 ms)
                + _step(3, 950, SITES_C, **kw))


def _reader(name):
    return R.reader(tiny_cell.REPO, name)


def test_split_adds_up_to_dispatch():
    steps = D.steps(_three_steps())
    assert len(steps) == 3
    for s in steps:
        parts = (s["launch_ms"] + s["state_ms"] + s["route_fetch_ms"]
                 + s["self_ms"])
        assert parts == pytest.approx(s["dispatch_ms"]) == 0.08
    assert [s["self_ms"] for s in steps] == pytest.approx([0.017, 0.02,
                                                           0.005])


@pytest.mark.parametrize("name,want", [
    ("runtime.launch_ms", 0.033), ("runtime.state_ms", 0.02),
    ("runtime.route_fetch_ms", 0.01), ("runtime.dispatch_self_ms", 0.017)])
def test_time_readers_take_the_median_step(name, want):
    read = _reader(name)
    assert read(_three_steps()) == pytest.approx(want)
    # no decode step in the window, or a program without the site spans
    assert read(_three_steps() | {"window": (2.0, 3.0)}) is None
    assert read(_three_steps(programs=False)) is None


def test_programs_per_token_reader():
    read = _reader("runtime.programs_per_token")
    # 5 + 2 + 2 programs over 1 + 2 + 1 decoded tokens
    run = _run(_step(0, 0, SITES_A) + _step(1, 200, SITES_B, decode_rows=2)
               + _step(2, 400, SITES_C))
    assert read(run) == pytest.approx(9 / 4)
    assert read(run | {"window": (2.0, 3.0)}) is None
    assert read(_three_steps(programs=False)) is None


def test_split_of_a_served_packed_run(tiny_moe_cfg, tiny_moe_params):
    """The readers on the program's own spans: batch-1 offloaded decode
    with speculative staging, paged KV and chunked prefill."""
    from repro.configs.base import OffloadSpec
    from repro.core.offload_engine import OffloadEngine
    from repro.obs import Telemetry
    from repro.serving.engine import ContinuousEngine
    off = OffloadEngine(tiny_moe_params, tiny_moe_cfg,
                        OffloadSpec(cache_size=4, num_speculative=2,
                                    expert_bits=3, attn_bits=4),
                        quantized=True)
    tel = Telemetry(timing=True, trace=True)
    eng = ContinuousEngine(tiny_moe_params, tiny_moe_cfg, max_slots=1,
                           slot_len=40, eos_id=None, kv_page=8,
                           prefill_chunk=8, offload=off, telemetry=tel)
    rng = np.random.default_rng(3)
    for n in (12, 9):
        eng.submit(rng.integers(1, tiny_moe_cfg.vocab_size, n)
                   .astype(np.int32), 5)
    eng.run(max_steps=100)
    run = _run(tel.tracer.events, (-1e9, 1e9))
    run["telemetry_t0_ns"] = tel.tracer._t0
    steps = D.steps(run)
    assert len(steps) == 2 * 4  # 5 tokens a request, the first from prefill
    n_moe = off.n_moe_layers
    for s in steps:
        parts = (s["launch_ms"] + s["state_ms"] + s["route_fetch_ms"]
                 + s["self_ms"])
        assert parts == pytest.approx(s["dispatch_ms"], rel=1e-9)
        assert min(s["launch_ms"], s["state_ms"], s["route_fetch_ms"],
                   s["self_ms"]) > 0
        # embed + head, mixer + moe per layer, stages for lookahead 1
        assert s["programs"] == 2 + 2 * n_moe + (n_moe - 1)
    assert _reader("runtime.programs_per_token")(run) == 2 + 3 * n_moe - 1
    for name in ("launch_ms", "state_ms", "route_fetch_ms",
                 "dispatch_self_ms"):
        assert _reader("runtime." + name)(run) > 0
