"""The comparison that decides ``correct``: served tokens against the
reference's logits.

After the window, a sample of the finished requests, drawn from the seed
and holding the one with the longest sequence, is run through the
reference once each: prompt and served tokens together, every position at
once.  For each served token the number compared is the gap by which the
reference's logit of that token lies below the reference's best logit at
its position (0 where the program picked the reference's own best).  Greedy
decoding makes the gap of a sound run a matter of rounding only.  The
widest gap over the sample is held to the configuration's limit.

`control_gaps` reads the same gap for the token that the reference computed
in a lower precision puts first: the control that has to fail the limit.
"""

from __future__ import annotations

import numpy as np


def sample(finished: list, seed: int, want: dict) -> list:
    """Requests to compare: the longest sequence first, then others in a
    seeded order until ``want["tokens"]`` served tokens and
    ``want.get("requests", 1)`` requests are covered.  Enough requests
    make a fault that spares some slots show in the sample."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (len(r.prompt) + len(r.out),
                                             r.index))
    chosen = [by_len[-1]]
    rest = by_len[:-1]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    for i in rng.permutation(len(rest)):
        if (sum(len(r.out) for r in chosen) >= want["tokens"]
                and len(chosen) >= want.get("requests", 1)):
            break
        chosen.append(rest[int(i)])
    return chosen


def _positions(r):
    """The sequence fed to the reference and the rows of its served
    tokens: token j of the answer is predicted at position L - 1 + j."""
    seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
    rows = np.arange(len(r.prompt) - 1, len(seq))
    return seq, rows


def served_gaps(ref, requests: list, pad_to: int) -> np.ndarray:
    """Gap of every served token of ``requests`` against the reference."""
    out = []
    for r in requests:
        seq, rows = _positions(r)
        lg = ref.logits(seq, pad_to)[rows]
        served = np.asarray(r.out, dtype=np.int64)
        out.append(lg.max(-1) - lg[np.arange(len(rows)), served])
    return np.concatenate(out) if out else np.zeros(0)


def control_gaps(ref, requests: list, pad_to: int,
                 precision: str) -> np.ndarray:
    """Gap of the token that the reference in ``precision`` puts first, at
    every position where ``requests`` served a token."""
    out = []
    for r in requests:
        seq, rows = _positions(r)
        lg = ref.logits(seq, pad_to)[rows]
        pick = ref.logits(seq, pad_to, precision)[rows].argmax(-1)
        out.append(lg.max(-1) - lg[np.arange(len(rows)), pick])
    return np.concatenate(out) if out else np.zeros(0)


def judge(gaps: np.ndarray, limits: dict) -> dict:
    """Each number compared, beside its limit, and the verdict."""
    worst = float(gaps.max()) if gaps.size else float("nan")
    compared = {
        "max_logit_gap": {"value": worst,
                          "limit": limits["max_logit_gap"],
                          "at_most": True},
        "tokens_compared": {"value": int(gaps.size),
                            "limit": limits["min_tokens"],
                            "at_most": False},
    }
    ok = all((c["value"] <= c["limit"]) if c["at_most"]
             else (c["value"] >= c["limit"]) for c in compared.values())
    return {"correct": bool(ok and np.isfinite(worst)), "compared": compared}
