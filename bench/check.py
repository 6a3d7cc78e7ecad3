"""Decide ``correct``: greedy requests served by the program after the
window, set beside the plain reference.

Once the window has closed, the run serves greedy requests drawn from the
seed (:func:`requests`) through the same engine, programs and slots as
the window's, at the mix's sizes, the longest first, until
``TARGET_TOKENS`` output tokens are planned (at most ``MAX_REQUESTS``),
and keeps the logits each token was chosen from.  The reference runs once
over each prompt with its served tokens (teacher forcing).  Two numbers
are compared, each with the cell's limit (``bench/limits/<cell>.json``):

* ``token_gap``: the share of the served tokens whose reference logit
  lies more than ``GAP_OVER`` below the reference's best logit at their
  position.  It catches a token altered where it is produced, and a
  change of precision that flips many near-ties; one flip in a few
  hundred tokens, which bfloat16 rounding of a near-tie makes, reads
  well under the limit.  (The widest single gap cannot tell the two
  apart: the served program's swings from seed to seed with one such
  flip, as far as the lowest control reading.)
* ``logit_err``: the mean, over the served positions, of ``max |program
  - reference|`` of a row over that row's largest ``|reference|``.  It
  catches a change of precision or of the mathematics however the tokens
  fall, in proportion to the share of rows it moves.

A control (the reference at a lower activation precision) is put in the
program's place on the same prompts and tokens: the token it puts first
at each position stands for the served one, and its rows for the
program's.

Beside them, with no limit: ``token_flips``, how many served tokens are
not the reference's best at their position (a gap above 0);
``token_gap_max``, the widest gap; and ``token_gap_pos``, the served
position (counted over the requests in order) where it lies.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np

import generator as G

TARGET_TOKENS = 192
MAX_REQUESTS = 6
PAD = 256  # reference sequences are padded to a multiple of this length
# a served token more than this many logits below the reference's best
# counts toward ``token_gap``: above the 95th percentile of the served
# program's gaps in every chip run (0.055 at most; PERF.md, section 4)
GAP_OVER = 0.1


def requests(mix, seed: int, vocab: int):
    """The check's greedy requests: a stream of the mix's sizes of its own,
    the longest output first, until ``TARGET_TOKENS`` are planned."""
    pool = G.generate(mix, seed, vocab, 4 * MAX_REQUESTS, greedy=True,
                      stream=1)
    pool.sort(key=lambda r: (-r.max_new, -len(r.prompt), r.index))
    out, n = [], 0
    for r in pool:
        if n >= TARGET_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(r)
        n += r.max_new
    return out


def load_reference(root: Path, config):
    path = root / "bench" / "reference" / f"{config['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{config['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def sequences(picked):
    """Prompt + served tokens (the last served token is never an input),
    padded with token 0 to a multiple of ``PAD``: padding sits after
    every compared position, and attention is causal."""
    out = []
    for r in picked:
        s = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        n = -(-len(s) // PAD) * PAD
        out.append(np.pad(s, (0, n - len(s))))
    return out


def program_rows(r, vocab: int):
    """(tokens, vocab) float32: the logits each served token came from."""
    return np.stack([np.asarray(a)[i, :vocab] for a, i in r.logits]
                    ).astype(np.float32)


def rel_err(rows, ref_rows) -> np.ndarray:
    """Per row: max |rows - ref| over that row's max |ref|."""
    err = np.abs(rows - ref_rows).max(-1)
    return err / np.maximum(np.abs(ref_rows).max(-1), 1e-30)


def token_gaps(ref_logits, served, first_pos, choose=None) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best at its position.  ``served[i]`` was produced at
    position ``first_pos + i``; ``choose`` replaces the served tokens with
    another model's choice (the argmax of its logits at those rows)."""
    rows = ref_logits[first_pos: first_pos + len(served)]
    toks = np.asarray(served) if choose is None else \
        choose[first_pos: first_pos + len(served)].argmax(-1)
    return rows.max(-1) - rows[np.arange(len(rows)), toks]


def readings(ref_logits, picked, rows, choose=None):
    """The compared numbers, and beside them the token flips, the widest
    gap and the spread of the row errors.  ``rows``: per request, the
    logits its tokens were chosen from; ``choose``: per request, a model's
    logits whose argmax stands for the served tokens."""
    gaps, errs = [], []
    for i, r in enumerate(picked):
        lo = len(r.prompt) - 1
        ref = ref_logits[i]
        gaps.append(token_gaps(ref, r.tokens, lo,
                               None if choose is None else choose[i]))
        if rows[i] is not None:
            errs.append(rel_err(rows[i], ref[lo: lo + len(r.tokens)]))
    g = np.concatenate(gaps)
    out = {"token_gap": float((g > GAP_OVER).mean()), "logit_err": None,
           "tokens": sum(len(r.tokens) for r in picked),
           "token_flips": int((g > 0).sum()),
           "token_gap_max": float(g.max()),
           "token_gap_pos": int(g.argmax())}
    if len(errs) == len(picked):
        e = np.concatenate(errs)
        out.update(logit_err=float(e.mean()),
                   logit_err_median=float(np.median(e)),
                   logit_err_p90=float(np.quantile(e, 0.9)),
                   logit_err_max=float(e.max()))
    return out


def compare(root: Path, config, seed: int, picked, controls=()):
    """{"program": the compared numbers of the served requests, and for
    each entry c of ``controls`` (an activation precision of the
    reference, such as "fp8"), c: the same numbers with that control in
    the program's place}.  A request that did not finish, or whose logits
    were not all kept, gives no numbers."""
    done = [r for r in picked if r.status == "completed" and r.tokens
            and len(r.tokens) == r.max_new]
    if not picked or len(done) < len(picked):
        empty = {"token_gap": None, "logit_err": None, "tokens": 0}
        return {c: dict(empty) for c in ("program",) + tuple(controls)}
    mod = load_reference(root, config)
    ref = mod.Reference(config, seed)
    V = config["model"]["vocab_size"]
    # the program's rows come off the device before the reference runs
    prog = [program_rows(r, V) if len(r.logits) == len(r.tokens) else None
            for r in picked]
    logits = ref.logits(sequences(picked), acts=("",) + tuple(controls))
    out = {"program": readings(logits[""], picked, prog)}
    for c in controls:
        rows = [a[len(r.prompt) - 1: len(r.prompt) - 1 + len(r.tokens)]
                for a, r in zip(logits[c], picked)]
        out[c] = readings(logits[""], picked, rows, logits[c])
    return out
