"""Paper Table 2: tokens/s on {A100, 3080M, 3060, T4} x {2-bit, 3-bit
experts} x {full algorithm, w/o pre-loading, w/o LRU & pre-loading,
naive offloading}.

Cache/speculation statistics are MEASURED (trace replay of the trained
router through the actual policies, k=4/n_spec=2 per the paper's 16GB
operating point); wall-clock is the calibrated analytic cost model at
Mixtral-8x7B parameter sizes (no GPU on this host — see DESIGN.md §2).
The reproduced claims are the orderings and ratios of Table 2."""
from __future__ import annotations

from repro.configs import get_config
from repro.core import cost_model as C

from benchmarks.common import emit, get_trace

POLICY_LABELS = {
    "full": "Full algorithm",
    "no_spec": "W/o expert pre-loading",
    "no_lru_no_spec": "W/o LRU cache & pre-loading",
    "naive": "Naive offloading (per-layer streaming)",
}

PAPER_TABLE2 = {  # tokens/s from the paper, for side-by-side reporting
    (2, "full"): {"a100": 3.061, "3080m": 2.655, "3060": 2.278, "t4": 2.092},
    (2, "no_spec"): {"a100": 2.918, "3080m": 2.227, "3060": 2.051, "t4": 1.567},
    (2, "no_lru_no_spec"): {"a100": 2.265, "3080m": 1.758, "3060": 1.547, "t4": 1.168},
    (2, "naive"): {"a100": 1.392, "3080m": 1.059, "3060": 0.919, "t4": 0.661},
    (3, "full"): {"a100": 2.845, "3080m": 2.475, "3060": 2.038, "t4": 1.603},
    (3, "no_spec"): {"a100": 2.683, "3080m": 2.024, "3060": 1.857, "t4": 1.365},
    (3, "no_lru_no_spec"): {"a100": 2.055, "3080m": 1.595, "3060": 1.346, "t4": 1.061},
    (3, "naive"): {"a100": 1.246, "3080m": 0.914, "3060": 0.580, "t4": 0.580},
}


def run(quick=False):
    tr = get_trace(128 if quick else None)
    mixtral = get_config("mixtral-8x7b")
    stats = C.replay_policies(tr["ids"], tr["hiddens"], tr["routers"],
                              k=4, n_spec=2, lookahead=1)
    # tiny-moe has 6 MoE layers; project per-token transfer counts to
    # Mixtral's 32 MoE layers (per-layer rates are what the trace measures)
    layer_scale = mixtral.moe_layer_count / tr["ids"].shape[1]
    stats = {pol: C.TokenStats(*(v * layer_scale for v in
                                 (ts.demand_loads, ts.spec_loads,
                                  ts.hits, ts.spec_hits)))
             for pol, ts in stats.items()}
    rows = []
    ours = {}
    for bits in (2, 3):
        for pol, ts in stats.items():
            for hw_name, hw in C.HARDWARE.items():
                tps = C.tokens_per_second(mixtral, hw, ts, bits,
                                          naive=(pol == "naive"))
                ours[(bits, pol, hw_name)] = tps
                paper = PAPER_TABLE2.get((bits, pol), {}).get(hw_name)
                rows.append({
                    "name": f"table2_{bits}bit_{pol}_{hw_name}",
                    "us_per_call": f"{1e6 / tps:.0f}",
                    "derived": f"tok/s={tps:.3f};paper={paper}",
                    "bits": bits, "policy": pol, "hw": hw_name,
                    "tokens_per_s": round(tps, 3), "paper_tokens_per_s": paper,
                })
    # reproduced structural claims
    claims = {
        # every policy level strictly improves throughput (per hw, 2-bit)
        "table2_policy_ordering": all(
            ours[(2, "full", h)] > ours[(2, "no_spec", h)]
            > ours[(2, "no_lru_no_spec", h)] > ours[(2, "naive", h)]
            for h in C.HARDWARE),
        # full algorithm lands in the paper's 2-4 tok/s interactive band
        "table2_interactive_band": all(
            1.5 < ours[(b, "full", h)] < 5.0
            for b in (2, 3) for h in C.HARDWARE),
        # hw ordering follows bandwidth: a100 > 3080m > 3060 > t4
        "table2_hw_ordering": all(
            ours[(b, "full", "a100")] > ours[(b, "full", "3080m")]
            > ours[(b, "full", "3060")] > ours[(b, "full", "t4")]
            for b in (2, 3)),
    }
    for nm, ok in claims.items():
        rows.append({"name": nm, "derived": str(ok)})
        print(f"[table2] {nm}: {ok}")
    # stats summary for the writeup
    ts = stats["full"]
    rows.append({
        "name": "table2_measured_stats_full",
        "derived": (f"demand/tok={ts.demand_loads:.2f};"
                    f"spec_hits/tok={ts.spec_hits:.2f};"
                    f"hits/tok={ts.hits:.2f};spec_loads/tok={ts.spec_loads:.2f}"),
    })
    emit(rows, "table2_speed")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
