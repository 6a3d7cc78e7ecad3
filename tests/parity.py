"""Shared cross-plane parity harness (no ``test_`` prefix — imported,
not collected).

The repo's load-bearing acceptance invariant is that every execution
plane, KV layout and admission mode emits the SAME greedy token stream
— and, where the design says so, the same h2d transfer counters.  That
invariant used to be asserted by hand-rolled loops scattered across
``test_offload.py`` / ``test_runtime.py`` / ``test_paged_kv.py``; this
module is the one implementation they (and the speculative-decoding
matrix in ``test_spec_decode.py``) all drive, so a new plane or KV
layout gets the whole grid by adding one factory entry.

Pieces:

* :func:`make_prompts` / :func:`oracle_streams` — seeded workloads and
  the ``generate_plain`` B=1 oracle every engine must reproduce.
* :func:`run_offload_generate` / :func:`offload_plane_engines` — the
  batch OffloadEngine across its planes (packed pipelined / vectorized
  / PR-2 sync / accounting replay) with measured-counter extraction.
* :func:`run_continuous` + :data:`CONTINUOUS_KV_VARIANTS` — the
  continuous engine across KV layouts (dense / paged / pinned-horizon
  paged) and admission modes (whole-prompt / chunked / budgeted).
* :func:`assert_tokens_equal` / :func:`offload_counters` /
  :func:`continuous_counters` — the equality assertions, with readable
  divergence output.
* :func:`assert_close_f32` / :func:`assert_states_close_f32` — logits and
  decode state of two float32 programs that compute the same values with
  differently shaped or ordered reductions (:data:`F32_REASSOC_TOL`).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import numpy as np

from repro.core.offload_engine import OffloadEngine, generate_plain
from repro.serving.engine import ContinuousEngine

# the four measured transfer counters every offload plane must agree on
OFFLOAD_COUNTERS = ("hits", "spec_hits", "demand_loads", "spec_loads")
# the continuous engine's legacy-flat h2d keys (offloaded mode)
CONTINUOUS_H2D_KEYS = ("offload_demand_loads", "offload_spec_loads",
                       "offload_bytes_h2d")

# ContinuousEngine constructor overlays, keyed by variant name — the KV
# layout x admission grid the parity tests sweep.  ``paged_exact`` pins
# the table horizon (bitwise-logits mode); the others are the perf modes
# whose greedy token streams must still match.
CONTINUOUS_KV_VARIANTS: Dict[str, dict] = {
    "dense": {},
    "dense_chunked": dict(prefill_chunk=4),
    "paged": dict(kv_page=16),
    "paged_exact": dict(kv_page=16, ragged_bucket=False),
    "paged_chunked": dict(kv_page=16, prefill_chunk=4),
    # prefix-reuse mode (DESIGN.md §13): cached full pages are adopted
    # at admission and prefill starts at the divergence point — the
    # greedy stream must stay bitwise whether or not any prompt hits
    "paged_prefix": dict(kv_page=8, prefix_cache_pages=8),
}


def make_prompts(cfg, lens: Sequence[int], seed: int = 1
                 ) -> List[np.ndarray]:
    """Seeded random prompts (token 0 excluded — it is the pad id)."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
            for n in lens]


def make_extras(cfg, n: int, seed: int = 3) -> List[dict]:
    """Per-request admission extras: enc-dec archs get a seeded
    ``audio_embeds`` frontend output each; everything else gets None."""
    if not cfg.is_encoder_decoder:
        return [None] * n
    rng = np.random.default_rng(seed)
    return [{"audio_embeds": rng.standard_normal(
        (cfg.encoder_seq, cfg.d_model)).astype(np.float32)}
        for _ in range(n)]


def oracle_streams(params, cfg, prompts, max_news,
                   extras=None) -> List[List[int]]:
    """The B=1 ``generate_plain`` greedy stream per request — the
    reference every engine/plane/layout must reproduce bitwise."""
    extras = extras or [None] * len(prompts)

    def batched(e):  # the B=1 oracle wants a leading batch axis
        if e is None:
            return None
        return {"audio_embeds": np.asarray(e["audio_embeds"],
                                           np.float32)[None]}

    return [generate_plain(params, cfg, p[None], m,
                           extras=batched(e))[0].tolist()
            for p, m, e in zip(prompts, max_news, extras)]


def assert_tokens_equal(got, want, label: str) -> None:
    assert got == want, (f"{label}: token stream diverged\n"
                         f"  got : {got}\n  want: {want}")


# Two float32 programs computing the same values with differently shaped
# reductions — a prefill chunk changes a matmul's row count, so XLA tiles
# and sums it in another order; a fused batched dequant-matmul contracts
# in another order than a gather einsum — differ by float32
# reassociation: ~sqrt(K) * 2^-24 of the output scale per layer, ~1e-6
# over these shallow stacks (measured: at most 3e-6 of it).  A wrong
# mask, position, recurrent carry or expert moves the output by its own
# scale.  Equal bits are promised only by identical programs on
# identical shapes, and no accelerator keeps even that.
F32_REASSOC_TOL = 1e-5


def assert_close_f32(got, want, label: str,
                     tol: float = F32_REASSOC_TOL) -> None:
    """Float arrays: ``max |got - want| <= tol * max |want|``.  Integer
    arrays (positions, page tables) must match exactly."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (label, got.shape, want.shape)
    if not np.issubdtype(want.dtype, np.floating):
        np.testing.assert_array_equal(got, want, err_msg=label)
        return
    scale = max(float(np.abs(want).max()), 1e-30)
    err = float(np.abs(got.astype(np.float64) - want).max())
    assert err <= tol * scale, (f"{label}: max abs err {err} over "
                                f"{tol} x max |ref| {scale}")


def assert_states_close_f32(got, want, label: str) -> None:
    """:func:`assert_close_f32` on every leaf of two decode states."""
    la, lb = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(la) == len(lb), label
    for i, (a, b) in enumerate(zip(la, lb)):
        assert_close_f32(a, b, f"{label} (state leaf {i})")


# ----------------------------------------------------------------------
# batch OffloadEngine drivers
def offload_counters(stats):
    """OffloadStats -> the measured transfer-counter tuple."""
    return tuple(getattr(stats, k) for k in OFFLOAD_COUNTERS)


def run_offload_generate(eng: OffloadEngine, prompt, max_new: int, **kw):
    """One B=1 generation -> (token list, OffloadStats)."""
    prompt = np.asarray(prompt, np.int32).reshape(1, -1)
    out, stats = eng.generate(prompt, max_new, **kw)
    return out[0].tolist(), stats


def offload_plane_engines(params, qdeq, cfg, spec
                          ) -> Dict[str, OffloadEngine]:
    """The offload engine across its execution planes.  ``qdeq`` is the
    dequantized model from ``quantize_for_offload`` — the accounting
    plane decodes it so its tokens are comparable bitwise with the
    packed planes (which execute the same quantized weights)."""
    return {
        "packed_pipelined": OffloadEngine(params, cfg, spec,
                                          quantized=True),
        "packed_vectorized": OffloadEngine(params, cfg, spec,
                                           quantized=True,
                                           pipelined=False),
        "packed_sync": OffloadEngine(params, cfg, spec, quantized=True,
                                     pipelined=False, vectorized=False),
        "accounting": OffloadEngine(qdeq, cfg, spec, quantized=False),
    }


# ----------------------------------------------------------------------
# ContinuousEngine driver
def run_continuous(params, cfg, prompts, max_news, *, max_slots: int = 2,
                   slot_len: int = 64, eos_id=None, max_steps: int = 800,
                   extras=None, **kw):
    """Build, submit, drain -> (per-request token lists, engine).
    Asserts every request actually finished (a hung engine must fail
    the parity test, not time out silently)."""
    eng = ContinuousEngine(params, cfg, max_slots=max_slots,
                           slot_len=slot_len, eos_id=eos_id, **kw)
    extras = extras or [None] * len(prompts)
    reqs = [eng.submit(p, m, extras=e)
            for p, m, e in zip(prompts, max_news, extras)]
    eng.run(max_steps=max_steps)
    unfinished = [r.rid for r in reqs if r.state != "finished"]
    assert not unfinished, f"requests never finished: {unfinished}"
    return [r.generated for r in reqs], eng


def continuous_counters(eng: ContinuousEngine) -> Dict[str, float]:
    """The offloaded continuous engine's h2d counters (legacy-flat)."""
    s = eng.stats()
    return {k: s[k] for k in CONTINUOUS_H2D_KEYS}
