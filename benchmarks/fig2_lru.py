"""Paper Fig. 2 (left): LRU cache hit ratio vs cache size k.

Measured by replaying the trained MoE's real routing trace through the
LRU cache at each k (the paper runs Mixtral over OpenAssistant; we run
tiny-moe — same 8-expert top-2 routing — over held-out corpus text)."""
from __future__ import annotations

from repro.core.lru_cache import lru_hit_curve

from benchmarks.common import emit, get_trace


def run(quick=False):
    tr = get_trace(128 if quick else None)
    ks = [1, 2, 3, 4, 6, 8]
    curve = lru_hit_curve(tr["ids"], ks)
    rows = []
    for k in ks:
        rows.append({
            "name": f"fig2_lru_hit_ratio_k{k}",
            "us_per_call": "",
            "derived": f"{curve[k]:.4f}",
            "k": k,
            "hit_ratio": curve[k],
        })
    # paper-claim check: hit ratio rises steeply then saturates; k=E is ~1
    rows.append({
        "name": "fig2_lru_monotone",
        "derived": str(all(curve[a] <= curve[b] + 1e-9
                           for a, b in zip(ks, ks[1:]))),
    })
    # beyond-paper: how much headroom does LRU leave vs LFU-decay and the
    # clairvoyant Belady bound? (paper section 3.1 names this open)
    from repro.core.lru_cache import policy_comparison

    comp = policy_comparison(tr["ids"], [2, 4])
    for (pol, k), v in sorted(comp.items()):
        rows.append({"name": f"fig2ext_{pol}_k{k}", "us_per_call": "",
                     "derived": f"{v:.4f}", "policy": pol, "k": k,
                     "hit_ratio": v})
    emit(rows, "fig2_lru")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
