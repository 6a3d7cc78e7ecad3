"""The traffic generator: one seed, one request stream; every seed, the
same sizes in another order."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

import tiny_cell  # noqa: F401  (puts bench/ on the path)
import generator as G

MIXES = sorted((tiny_cell.BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_requests(path):
    mix = json.loads(Path(path).read_text())
    a = G.generate(mix, 2 ** 31 + 5, 1000, 48)
    b = G.generate(mix, 2 ** 31 + 5, 1000, 48)
    assert [(r.prompt.tolist(), r.max_new, r.greedy, r.gap_s) for r in a] \
        == [(r.prompt.tolist(), r.max_new, r.greedy, r.gap_s) for r in b]


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_seeds_share_sizes_not_order(path):
    mix = json.loads(Path(path).read_text())
    n = 4 * int(mix["strata"])
    a = G.generate(mix, 1, 1000, n)
    b = G.generate(mix, 2 ** 33 + 1, 1000, n)
    assert Counter(len(r.prompt) for r in a) == Counter(len(r.prompt)
                                                        for r in b)
    assert Counter(r.max_new for r in a) == Counter(r.max_new for r in b)
    assert sum(r.greedy for r in a) == sum(r.greedy for r in b)
    plo, phi = G.length_range(mix["prompt"])
    olo, ohi = G.length_range(mix["output"])
    for r in a:
        assert plo <= len(r.prompt) <= phi
        assert len(r.prompt) % mix["prompt"].get("round_up", 1) == 0
        assert olo <= r.max_new <= ohi
        assert (0 <= r.prompt).all() and (r.prompt < 1000).all()
    assert not any(r.greedy for r in a)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_check_requests_are_greedy_at_the_mix_sizes(path):
    """The check's requests: greedy, of sizes the mix can send, the same
    for the same seed, another stream than the timed traffic's."""
    import check as C
    mix = json.loads(Path(path).read_text())
    a = C.requests(mix, 2 ** 31 + 7, 1000)
    b = C.requests(mix, 2 ** 31 + 7, 1000)
    assert a and all(r.greedy for r in a)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    assert sum(r.max_new for r in a[:-1]) < C.TARGET_TOKENS
    assert a[0].max_new == max(r.max_new for r in a)
    plo, phi = G.length_range(mix["prompt"])
    olo, ohi = G.length_range(mix["output"])
    assert all(plo <= len(r.prompt) <= phi and olo <= r.max_new <= ohi
               for r in a)
    timed = G.generate(mix, 2 ** 31 + 7, 1000, 1)[0]
    assert timed.prompt.tolist() != a[0].prompt.tolist()


def test_poisson_gaps_have_the_mix_rate():
    mix = dict(tiny_cell.MIX_OPEN, strata=16)
    reqs = G.generate(mix, 9, 100, 160)
    gaps = np.asarray([r.gap_s for r in reqs])
    rate = mix["arrival"]["rate"]
    assert abs(gaps.mean() * rate - 1.0) < 0.05
    other = np.asarray([r.gap_s for r in G.generate(mix, 10, 100, 160)])
    assert np.allclose(np.sort(gaps), np.sort(other))


def test_possible_prompt_lengths():
    mix = {"prompt": {"dist": "lognormal", "min": 64, "max": 1024,
                      "round_up": 64}}
    assert G.possible_prompt_lengths({"prompt": {"dist": "fixed",
                                                 "value": 256}}) == [256]
    assert G.possible_prompt_lengths(mix) == list(range(64, 1025, 64))
