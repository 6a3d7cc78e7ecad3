"""A Mixtral-shaped cell on the CPU: the registry's ``mixtral-offload``
cut to a small width, with what sets Mixtral apart from the Granite cell
kept (8 experts top-2, 2-bit experts at group 16, an untied head, no
window, heads of width 128), served through ``bench/run.py`` and set
beside the plain reference that the Mixtral cell uses
(``bench/reference/moe_transformer_by_layer.py``); and the
``mixtral-offload.l4`` configuration file against the program's
registry.

The root is written the way ``tiny_cell.make_root`` writes its own: a
copy of ``bench/`` with this cell's configuration, mix and limits added
as files and entries.
"""
import json

import numpy as np
import pytest

import tiny_cell

CONFIG = {
    "name": "mixtral-small.l2",
    "source": "in-repo mixtral-offload (src/repro/configs/mixtral_offload.py)"
              " at d_model 256, 2 layers",
    "registry": "mixtral-offload",
    "overrides": {"n_layers": 2, "block_pattern": ["attn+moe"],
                  "sliding_window": None, "d_model": 256, "n_heads": 2,
                  "n_kv_heads": 1, "head_dim": 128, "d_ff": 256,
                  "vocab_size": 512, "dtype": "float32"},
    "reference": "moe_transformer_by_layer",
    "model": {"block_pattern": ["attn+moe"], "n_layers": 2, "d_model": 256,
              "n_heads": 2, "n_kv_heads": 1, "head_dim": 128, "d_ff": 256,
              "vocab_size": 512, "num_experts": 8, "top_k": 2,
              "rope_theta": 1000000.0, "norm_eps": 1e-05,
              "sliding_window": None, "tie_embeddings": False,
              "dtype": "float32"},
    "quant": {"experts": {"bits": 2, "group_size": 16, "scale_group": 128},
              "wo": {"bits": 4, "group_size": 64, "scale_group": 256},
              "wq_wk_wv": {"bits": 16},
              "hqq": {"iters": 20, "lp": 0.7, "beta0": 10.0, "kappa": 1.01}},
    "offload": {"cache_size": 4, "num_speculative": 2, "lookahead": 1},
    "serving": {"prefill_chunk": 8},
}
CELL = "mixtral-small.l2.tiny_b1"

# float32 program against the float32 reference on the same 2-bit codes:
# they differ only by float32 reassociation (the program's fused
# dequant-matmul and paged attention sum in their own order), ~1e-6 of a
# row's largest logit, and no served token lies below the reference's
# best; the fp8 control reads orders of magnitude more (asserted below)
LIMITS = {"token_gap": {"limit": 1e-3}, "logit_err": {"limit": 1e-3}}
# a served row against the reference's row at its position: float32
# reassociation over two layers and a 256-wide residual, as above, with
# a hundredfold room
ROW_TOL = 1e-4


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny_cell.make_root(tmp_path_factory.mktemp("bench"))
    tiny_cell.write_json(root / "bench" / "configs"
                         / f"{CONFIG['name']}.json", CONFIG)
    tiny_cell.write_json(root / "bench" / "limits" / f"{CELL}.json", LIMITS)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": CONFIG["name"], "source": CONFIG["source"],
        "file": f"bench/configs/{CONFIG['name']}.json", "reduced": [],
        "why": "CPU rehearsal of the Mixtral cell"})
    bench["workloads"].append({"name": CELL, "config": CONFIG["name"],
                               "traffic": "tiny_b1", "chips": 1,
                               "why": "CPU rehearsal"})
    tiny_cell.write_json(root / "BENCHMARK.json", bench)
    return root


def test_program_config_is_mixtral_shaped():
    import serve as S
    cfg = S.program_config(CONFIG)
    assert (cfg.moe.num_experts, cfg.moe.top_k) == (8, 2)
    assert cfg.offload.expert_bits == 2 and not cfg.tie_embeddings
    assert cfg.head_dim == 128 and cfg.sliding_window is None


def test_mixtral_shaped_cell_runs_correct(root):
    """The cell through ``run_cell`` (chunked prefill, then decode through
    the paged cache and the offload pool), checked against the reference;
    the fp8 control in the program's place reads incorrect."""
    out = tiny_cell.run(root, CELL, controls=("fp8",))
    assert out["correct"] is True, out["check"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["readings"]["tokens"] > 0
    for name, c in out["check"].items():
        assert 0 <= c["value"] <= c["limit"], name
    fp8 = out["controls"]["fp8"]
    assert fp8["correct"] is False
    assert fp8["readings"]["logit_err"] > 100 * out["readings"]["logit_err"]


def test_chunked_prefill_and_paged_decode_match_full_forward(root):
    """A greedy request served by the program (a 40-token prompt in
    8-token chunks, then 12 tokens decoded through 8-token KV pages and
    the pool of 4 + 2 speculative) gives, at every served position, the
    logits of the reference's full forward pass over the same tokens."""
    import check as C
    import generator as G
    import serve as S

    mix = json.loads((root / "bench" / "traffic" / "tiny_b1.json")
                     .read_text())
    seed = 2 ** 33 + 5
    cont, off = S.build(CONFIG, mix, seed)
    loop = S.Loop(cont, mix["temperature"])
    loop.keep_logits()
    rng = np.random.default_rng(seed)
    V = CONFIG["model"]["vocab_size"]
    req = G.Request(index=0, prompt=rng.integers(0, V, 40, dtype=np.int32),
                    max_new=12, greedy=True)
    loop.submit(req)
    loop.drain(float("inf"))
    assert req.status == "completed" and len(req.tokens) == 12
    m = cont.metrics()["offload"]
    assert m["hits"] + m["spec_hits"] + m["demand_loads"] > 0
    assert m["store_bytes"] == off.store.nbytes() and m["build_s"] > 0
    rows = C.program_rows(req, V)
    ref = C.load_reference(root, CONFIG).Reference(CONFIG, seed)
    seq = np.concatenate([req.prompt, np.asarray(req.tokens[:-1],
                                                 np.int32)])
    want = ref.logits([seq])[""][0][len(req.prompt) - 1:]
    assert rows.shape == want.shape == (12, V)
    err = C.rel_err(rows, want)
    assert err.max() < ROW_TOL, err


def test_l4_configuration_states_the_published_widths():
    """``mixtral-offload.l4``: every published width, cut in depth alone
    (4 of 32 layers), and the program's registry agrees with every size
    the file states."""
    import serve as S
    bench = json.loads((tiny_cell.REPO / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}["mixtral-offload.l4"]
    cfg_file = json.loads((tiny_cell.REPO / entry["file"]).read_text())
    assert entry["reduced"] == cfg_file["reduced"] == ["num_hidden_layers"]
    m, pub = cfg_file["model"], cfg_file["published"]
    assert m["n_layers"] == 4 < pub["num_hidden_layers"] == 32
    assert (m["d_model"], m["d_ff"], m["n_heads"], m["n_kv_heads"],
            m["head_dim"] * m["n_heads"]) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["hidden_size"])
    assert (m["num_experts"], m["top_k"], m["vocab_size"]) == (
        pub["num_local_experts"], pub["num_experts_per_tok"],
        pub["vocab_size"])
    assert m["tie_embeddings"] is pub["tie_word_embeddings"] is False
    assert m["sliding_window"] is pub["sliding_window"] is None
    assert (m["rope_theta"], m["norm_eps"]) == (pub["rope_theta"],
                                                pub["rms_norm_eps"])
    cfg = S.program_config(cfg_file)
    assert cfg.n_layers == 4 and cfg.offload.cache_size == 4
    cells = [(w["name"], w["traffic"], w["chips"])
             for w in bench["workloads"]
             if w["config"] == "mixtral-offload.l4"]
    assert ("mixtral-offload.l4.b1_closed", "b1_closed", 1) in cells


def test_by_layer_reference_holds_the_seeded_weights():
    """The Mixtral cell's reference makes, a layer at a time, bitwise the
    weights ``bench/weights.make_flat`` makes, and computes the logits
    ``moe_transformer`` computes from them."""
    import check as C
    import weights as W

    seed = 2 ** 32 + 9
    by_layer = C.load_reference(tiny_cell.REPO, CONFIG).Reference(CONFIG,
                                                                  seed)
    flat = W.make_flat(CONFIG["model"], seed)
    L, E = CONFIG["model"]["n_layers"], CONFIG["model"]["num_experts"]
    for path, want in flat.items():
        got = by_layer.w[path]
        if "experts" in path:
            for l in range(L):
                for e in range(E):
                    assert np.asarray(got[l, e]).tobytes() == \
                        np.asarray(want[l, e]).tobytes(), (path, l, e)
        else:
            assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    whole = C.load_reference(tiny_cell.REPO, dict(
        CONFIG, reference="moe_transformer")).Reference(CONFIG, seed)
    seq = np.arange(3, 3 + 256, dtype=np.int32) % CONFIG["model"]["vocab_size"]
    a = by_layer.logits([seq])[""][0]
    b = whole.logits([seq])[""][0]
    assert np.array_equal(a, b)
