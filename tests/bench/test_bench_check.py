"""The check's readings on hand-built logits, and the calibrated limits of
the benchmark's cells placed by their rule."""
import json
import math
from types import SimpleNamespace

import numpy as np
import pytest

import tiny_cell
import check as C

V = 6


def _request(prompt_len, tokens):
    return SimpleNamespace(prompt=np.zeros(prompt_len, np.int32),
                           tokens=list(tokens))


def _ref(rows):
    """Reference logits of a 2-token prompt and the served positions:
    position 0 is a prompt token's and is never compared."""
    return np.concatenate([np.full((1, V), 9.0, np.float32),
                           np.asarray(rows, np.float32)])


def test_readings_count_flips_and_find_the_widest_gap():
    # served positions 0..4: the best, a flip 0.5 below the best, a
    # near-tie flip 0.05 below, the best, a flip 2.0 below
    rows = [[0, 5, 1, 0, 0, 0],
            [0, 3, 3.5, 0, 0, 0],
            [0, 0, 0, 2.95, 3, 0],
            [4, 0, 0, 0, 0, 0],
            [0, 0, 0, 1, 3, 0]]
    picked = [_request(2, [1, 1, 3, 0, 3])]
    out = C.readings([_ref(rows)], picked, [np.asarray(rows, np.float32)])
    assert out["token_gap"] == pytest.approx(2 / 5)  # over GAP_OVER
    assert out["token_gap_max"] == pytest.approx(2.0)
    assert out["token_flips"] == 3
    assert out["token_gap_pos"] == 4
    assert out["tokens"] == 5
    assert out["logit_err"] == 0.0


def test_readings_count_positions_over_requests_in_order():
    a = [[1, 0, 0, 0, 0, 0], [0, 2, 0, 0, 0, 0]]
    b = [[0, 0, 1, 0, 0, 0], [0, 0, 0, 0, 0, 7]]
    picked = [_request(2, [0, 1]), _request(2, [2, 4])]  # b's last: 7 below
    out = C.readings([_ref(a), _ref(b)], picked, [None, None])
    assert out["token_gap"] == pytest.approx(1 / 4)
    assert out["token_gap_max"] == pytest.approx(7.0)
    assert out["token_flips"] == 1
    assert out["token_gap_pos"] == 3
    assert out["logit_err"] is None


def test_control_tokens_are_its_own_argmax():
    rows = [[0, 5, 1, 0, 0, 0], [0, 3, 3.5, 0, 0, 0]]
    control = _ref([[0, 0, 1, 0, 0, 0], [0, 3, 0, 0, 0, 0]])
    picked = [_request(2, [1, 2])]  # served tokens: the reference's best
    out = C.readings([_ref(rows)], picked, [None], [control])
    assert out["token_gap"] == pytest.approx(1.0)
    assert out["token_gap_max"] == pytest.approx(4.0)
    assert out["token_flips"] == 2
    assert out["token_gap_pos"] == 0


def _served_with_gaps(gaps):
    """Reference rows, served tokens: token 0 lies ``gaps[i]`` below the
    best (token 1) at served position i; a gap of 0 serves the best."""
    rows = np.zeros((len(gaps), V), np.float32)
    rows[:, 1] = 4.0
    rows[:, 0] = 4.0 - np.asarray(gaps, np.float32)
    toks = np.where(np.asarray(gaps) > 0, 0, 1)
    return _ref(rows), _request(2, toks)


def _cell_limit():
    path = tiny_cell.BENCH / "limits" / "granite-moe-1b-a400m.b1_closed.json"
    return json.loads(path.read_text())["token_gap"]["limit"]


def test_one_near_tie_flip_reads_under_the_limit():
    gaps = np.zeros(C.TARGET_TOKENS)
    gaps[100] = 0.5
    ref, req = _served_with_gaps(gaps)
    out = C.readings([ref], [req], [None])
    assert out["token_gap"] == pytest.approx(1 / C.TARGET_TOKENS)
    assert out["token_gap"] < _cell_limit()
    assert out["token_gap_max"] == pytest.approx(0.5)


def test_control_like_gaps_read_over_the_limit():
    """The fp8 control's lowest chip reading put 47 of 192 served tokens
    more than ``GAP_OVER`` below the reference's best; here they lie 0.11
    to 0.85 below it."""
    rng = np.random.default_rng(0)
    gaps = np.zeros(C.TARGET_TOKENS)
    idx = rng.choice(C.TARGET_TOKENS, 47, replace=False)
    gaps[idx] = rng.uniform(0.11, 0.85, 47)
    ref, req = _served_with_gaps(gaps)
    out = C.readings([ref], [req], [None])
    assert out["token_gap"] == pytest.approx(47 / C.TARGET_TOKENS)
    assert out["token_gap"] > _cell_limit()


def _limit_files():
    return sorted((tiny_cell.BENCH / "limits").glob("*.json"))


@pytest.mark.parametrize("path", _limit_files(), ids=lambda p: p.stem)
def test_limits_lie_between_program_and_control(path):
    """Each compared number's limit lies between P, the program's highest
    chip reading (``lower``), and Q, the control's lowest (``upper``):
    at sqrt(P * Q) where Q >= 2 P; ``logit_err`` keeps 0.1 where 0.1
    lies between 1.5 P and Q / 1.5."""
    limits = json.loads(path.read_text())
    assert set(limits) == {"token_gap", "logit_err"}
    for name, lim in limits.items():
        P, Q, limit = lim["lower"], lim["upper"], lim["limit"]
        assert 0 < P < limit < Q, name
        if name == "logit_err" and limit == 0.1:
            assert 1.5 * P <= limit <= Q / 1.5
        else:
            assert Q >= 2 * P, name
            assert limit == pytest.approx(math.sqrt(P * Q), rel=0.01), name
        assert "chip" in lim["readings"], name
