"""A ``tiny-moe`` stand-in cell for CPU rehearsals of ``bench/run.py``.

:func:`make_root` writes a checkout-like directory: a copy of ``bench/``,
a ``BENCHMARK.json`` whose only cells are tiny, and the tiny cells'
configuration, mix and limit files — added the way a later change adds a
cell, as files and entries.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

CONFIG = {
    "name": "tiny-moe.l2",
    "source": "in-repo tiny-moe (src/repro/configs/tiny_moe.py), 2 layers",
    "registry": "tiny-moe",
    "overrides": {"n_layers": 2},
    "reference": "moe_transformer",
    "model": {"block_pattern": ["swa+moe"], "n_layers": 2, "d_model": 256,
              "n_heads": 8, "n_kv_heads": 4, "head_dim": 32, "d_ff": 512,
              "vocab_size": 512, "num_experts": 8, "top_k": 2,
              "rope_theta": 10000.0, "norm_eps": 1e-05,
              "sliding_window": 256, "tie_embeddings": True,
              "dtype": "float32"},
    "quant": {"experts": {"bits": 3, "group_size": 64, "scale_group": 128},
              "wo": {"bits": 4, "group_size": 64, "scale_group": 256},
              "wq_wk_wv": {"bits": 16},
              "hqq": {"iters": 20, "lp": 0.7, "beta0": 10.0, "kappa": 1.01}},
    "offload": {"cache_size": 2, "num_speculative": 2, "lookahead": 1},
    "serving": {"prefill_chunk": 16},
}

MIX_B1 = {
    "arrival": {"kind": "closed", "clients": 1, "preroll_s": 0.0},
    "prompt": {"dist": "lognormal", "median": 32, "sigma": 0.5, "min": 16,
               "max": 64, "round_up": 16},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4,
               "max": 24},
    "temperature": 1.0, "strata": 4,
    "engine": {"max_slots": 1, "kv_page": 8},
}

MIX_OPEN = dict(MIX_B1, arrival={"kind": "poisson", "rate": 4.0,
                                 "preroll_s": 0.5},
                engine={"max_slots": 3, "kv_page": 8})

# float32 program against the float32 reference: no served token lies
# below the reference's best (token_gap 0.0 in every CPU run), and the
# mean logit error ~1e-5; the control (8-bit activations) reads orders of
# magnitude more
LIMITS = {"token_gap": {"limit": 1e-3}, "logit_err": {"limit": 1e-3}}


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1) + "\n")


def make_root(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    write_json(root / "bench" / "configs" / "tiny-moe.l2.json", CONFIG)
    write_json(root / "bench" / "traffic" / "tiny_b1.json", MIX_B1)
    write_json(root / "bench" / "traffic" / "tiny_open.json", MIX_OPEN)
    cells = []
    for mix in ("tiny_b1", "tiny_open"):
        name = f"tiny-moe.l2.{mix}"
        cells.append({"name": name, "config": "tiny-moe.l2",
                      "traffic": mix, "chips": 1, "why": "CPU rehearsal"})
        write_json(root / "bench" / "limits" / f"{name}.json", LIMITS)
    bench = dict(real, workloads=cells, configs=[{
        "name": "tiny-moe.l2", "source": CONFIG["source"],
        "file": "bench/configs/tiny-moe.l2.json", "reduced": [],
        "why": "CPU rehearsal"}])
    bench["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                          for m in real["per_layer"]]
    bench["end_to_end"] = [{k: v for k, v in m.items() if k != "workloads"}
                           for m in real["end_to_end"]]
    write_json(root / "BENCHMARK.json", bench)
    return root


def run(root: Path, cell: str, *, seed: int = 2 ** 31 + 11,
        seconds: float = 2.0, trace: bool = False, controls=(),
        hooks=None):
    """One in-process run of a tiny cell on the CPU (no chip check)."""
    import run as R
    spec = R.load_cell(root, cell)
    return R.run_cell(root, spec, seed, seconds, trace, require_chip=False,
                      controls=controls, hooks=hooks)
