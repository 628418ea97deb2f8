"""Whether a run's output is correct: the numbers compared, each with
its limit.

* ``max_logit_gap``, the model step.  Once the window has closed, a
  sample of the finished requests, drawn from the seed, with the
  longest among them and (where there are any) a LO request that was
  preempted and resumed and a HI request: the plain float32 reference
  (``bench/reference.py``) runs once over each prompt with its served
  tokens, and at every served position reads how far the served
  token's logit lies below the reference's best.  The widest such gap
  is compared with the configuration's limit
  (``correct.max_logit_gap``).  Greedy serving under bfloat16 may pick
  a near-tie of the reference; a wrong token lies far below it.
* ``lo_while_hi``, the scheduler: LO instructions dispatched while a
  HI request was admitted and unfinished.  Exact: limit 0.
* ``hi_unfinished``: HI requests that arrived in the window and had
  not finished a minute after it closed.  Limit 0.
* ``short_answers``: finished requests with fewer tokens than asked.
  Limit 0.
* ``conservation_broken``: 1 where the front door lost or invented a
  request (finished + live + queued != submitted).  Limit 0.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench import loadgen, reference


# the control's precision: the one below what the configuration serves in
CONTROL = {"bfloat16": "fp8", "float32": "bf16"}


def control_quant(conf: dict) -> str:
    return CONTROL[conf["serving"]["compute_dtype"]]


def sample(seed: int, finished: Sequence, n_max: int, tokens: int) -> List:
    """The requests the model check reads: the longest finished one,
    a preempted LO and a HI one where there are any, then others in a
    seed-drawn order, until ``tokens`` served tokens or ``n_max``."""
    if not finished:
        return []
    reqs = sorted(finished, key=lambda r: r.rid)
    order = [reqs[i] for i in loadgen.permutation(seed, "check",
                                                  len(reqs))]
    pick = [max(reqs, key=lambda r: (len(r.prompt) + len(r.generated),
                                     -r.rid))]
    for want in (lambda r: r.crit.value == "LO" and r.preemptions > 0,
                 lambda r: r.crit.value == "HI"):
        have = {p.rid for p in pick}
        r = next((r for r in order if want(r) and r.rid not in have), None)
        if r is not None:
            pick.append(r)
    for r in order:
        if len(pick) >= n_max or \
                sum(len(p.generated) for p in pick) >= tokens:
            break
        if r.rid not in {p.rid for p in pick}:
            pick.append(r)
    return pick[:n_max]


def gaps(conf: dict, params, req, quant: str = "none") -> np.ndarray:
    """Per served position, the reference's best logit less the logit of
    the token chosen there: the served token, or with ``quant`` the
    token that arithmetic at that precision puts first."""
    P, n = len(req.prompt), len(req.generated)
    seq = np.concatenate([np.asarray(req.prompt, np.int32),
                          np.asarray(req.generated[:-1], np.int32)])
    rows = np.arange(P - 1, P - 1 + n)
    ref = reference.logits_at(conf, params, seq, rows)
    if quant == "none":
        chosen = np.asarray(req.generated, np.int64)
    else:
        chosen = reference.logits_at(conf, params, seq, rows,
                                     quant=quant).argmax(-1)
    return ref.max(-1) - ref[np.arange(n), chosen]


def model_gap(conf: dict, params, picked: Sequence,
              quant: str = "none") -> float:
    if not picked:
        return float("inf")
    return float(max(gaps(conf, params, r, quant).max() for r in picked))


def numbers(conf: dict, params, window, seed: int) -> Dict[str, dict]:
    """Every number compared, with its limit, in report order."""
    finished = [r for r in window.hi + window.lo if r.done]
    c = conf["correct"]
    picked = sample(seed, finished, c["check_requests"], c["check_tokens"])
    short = sum(1 for r in finished if len(r.generated) < r.max_new_tokens)
    return {
        "max_logit_gap": {"value": model_gap(conf, params, picked),
                          "limit": c["max_logit_gap"],
                          "requests": len(picked),
                          "tokens": sum(len(r.generated) for r in picked)},
        "lo_while_hi": {"value": window.lo_while_hi, "limit": 0},
        "hi_unfinished": {"value": window.hi_unfinished, "limit": 0},
        "short_answers": {"value": short, "limit": 0},
        "conservation_broken": {"value": int(not window.conservation_ok),
                                "limit": 0},
    }


def passed(checks: Dict[str, dict]) -> bool:
    return all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in checks.values())
