"""How ``correct`` is decided: served greedy tokens against the reference.

Once the window has closed, a sample of the requests that produced
tokens, drawn from the seed and always holding the longest one, is run
through the plain reference (``bench/reference.py``) over its prompt and
its served tokens. At every served position the gap is the reference's
best logit minus the reference's logit of the token the program served
(0 where they agree). The widest gap over the sample is compared with
the configuration's ``check.logit_gap_limit``. Requests that finished
must also have served their whole budget (the traffic sets no stop
token), and none may have failed.

The control reads the same gap for the token that the reference computed
in float8 puts first at each position (``reference.logits(lower=True)``).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference
from bench.model import Model


def sample(done: Sequence[dict], k: int, seed: int) -> List[dict]:
    """k records (``prompt``, ``served``) drawn from the seed, with the
    longest (prompt and served tokens together) always among them. Drawn
    from the requests that finished; where fewer than k did, the requests
    the window's close cut short join them with their served tokens."""
    recs = [r for r in done if len(r["served"]) and r.get("finished", 1)]
    if len(recs) < k:
        recs += [r for r in done if len(r["served"])
                 and not r.get("finished", 1)]
    if not recs:
        return []
    recs = sorted(recs, key=lambda r: r["idx"])
    longest = max(range(len(recs)),
                  key=lambda i: (len(recs[i]["prompt"])
                                 + len(recs[i]["served"]), -i))
    rest = [i for i in range(len(recs)) if i != longest]
    rng = np.random.default_rng([int(seed), 2])
    pick = list(rng.permutation(rest)[: max(0, k - 1)])
    return [recs[i] for i in [longest] + sorted(pick)]


def gaps(m: Model, recs: Sequence[dict], pad_to: int, rows: int,
         lower: bool = False, times: Optional[Dict[str, float]] = None
         ) -> Tuple[np.ndarray, np.ndarray]:
    """(gap of each served token, gap of the control's first choice at the
    same positions, or an empty array unless ``lower``). ``times`` collects
    the reference's seconds by part."""
    seqs = [(np.concatenate([r["prompt"], r["served"]]).astype(np.int32),
             len(r["prompt"])) for r in recs]
    ref = reference.logits(m, seqs, pad_to, rows, times=times)
    served = np.concatenate([r["served"] for r in recs])
    z = np.concatenate(ref)
    best = z.max(-1)
    g = best - z[np.arange(len(served)), served]
    if not lower:
        return g, np.zeros((0,))
    low = np.concatenate(reference.logits(m, seqs, pad_to, rows, lower=True))
    pick = low.argmax(-1)
    return g, best - z[np.arange(len(pick)), pick]


def verdict(m: Model, gap: Optional[float], short: int,
            failed: int) -> Dict:
    """Each compared number beside its limit, and whether all hold."""
    numbers = {"logit_gap": [gap, m.logit_gap_limit],
               "short_requests": [short, 0],
               "failed_requests": [failed, 0]}
    ok = (gap is not None and gap <= m.logit_gap_limit and short == 0
          and failed == 0)
    return dict(correct=bool(ok), numbers=numbers)
