"""``bench/run.py`` end to end on a tiny stand-in cell, on the CPU, in
this process; and its refusal to run off the chip."""
import pytest

import tiny_cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_cell.make_root(tmp_path_factory.mktemp("bench"))


def test_closed_loop_cell_runs_and_checks(root):
    out = tiny_cell.run(root, "tiny-moe.l2.tiny_b1")
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 2
    m = out["metrics"]
    assert set(m) == {"tokens_s", "itl_p95_ms", "setup_s"}
    assert m["tokens_s"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0
    assert m["tokens_s"]["unit"] == "tokens/s"
    # a CPU run names its device and carries no device-trace numbers
    assert out["device"]["platform"] == "cpu"
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert list(out)[-1] == "check"
    assert set(out["check"]) == set(tiny_cell.LIMITS)
    for name, c in out["check"].items():
        assert c["limit"] == tiny_cell.LIMITS[name]["limit"]
        assert 0 <= c["value"] <= c["limit"]


def test_open_loop_cell_traced_reports_per_layer_metrics(root):
    out = tiny_cell.run(root, "tiny-moe.l2.tiny_open", trace=True)
    assert out["correct"] is True
    m = out["metrics"]
    # host-side per-layer metrics are read; those needing the chip's
    # peaks or a device trace are left out, never reported as 0
    assert {"offload.hit_ratio", "runtime.dispatch_ms"} <= set(m)
    for name in ("model.mfu", "model.hbm_share", "device.idle_share"):
        assert name not in m
    assert 0 < m["offload.hit_ratio"]["value"] <= 100
    assert "busy_s" not in out["device"]


def test_main_refuses_without_a_tpu(root, capsys):
    import run as R
    rc = R.main(["--workload", "tiny-moe.l2.tiny_b1", "--seed", "3",
                 "--seconds", "1", "--trace", "0"], root=root)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_unknown_workload_is_refused(root):
    import run as R
    with pytest.raises(SystemExit):
        R.load_cell(root, "no-such-cell")
