"""A new configuration, traffic mix and per-layer metric reach a run as
files and entries alone: no file of the harness is edited."""
import json

import tiny_cell


def test_new_files_are_found_by_name(tmp_path):
    root = tiny_cell.make_root(tmp_path)
    # a new mix: two closed-loop clients on two slots
    mix = dict(tiny_cell.MIX_B1, arrival={"kind": "closed", "clients": 2},
               engine={"max_slots": 2, "kv_page": 8})
    tiny_cell.write_json(root / "bench" / "traffic" / "two_clients.json",
                         mix)
    # a new per-layer metric: a reader of its own
    (root / "bench" / "metrics" / "tiny.steps_in_window.py").write_text(
        "import record as R\n\n\n"
        "def read(run):\n"
        "    n = sum(1 for t, _ in run['steps'] if R.in_window(run, t))\n"
        "    return float(n) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny-moe.l2.two_clients"
    bench["workloads"].append({"name": cell, "config": "tiny-moe.l2",
                               "traffic": "two_clients", "chips": 1,
                               "why": "discovery"})
    bench["per_layer"].append({"name": "tiny.steps_in_window",
                               "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "engine",
                               "moves": "tokens_s", "workloads": [cell]})
    tiny_cell.write_json(root / "BENCHMARK.json", bench)
    tiny_cell.write_json(root / "bench" / "limits" / f"{cell}.json",
                         tiny_cell.LIMITS)
    out = tiny_cell.run(root, cell, trace=True)
    assert out["correct"] is True
    assert out["metrics"]["tiny.steps_in_window"]["value"] >= 1
    assert out["metrics"]["tiny.steps_in_window"]["unit"] == "steps"
