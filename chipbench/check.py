"""The comparison that decides ``correct`` for a training cell.

Numbers, each against its limit from ``limits/<cell>.json``:

* ``loss_gap``: the largest |loss - reference loss| / reference loss over
  the checked steps;
* ``grad_gap``: over every leaf (every layer of a stacked leaf), the gap
  between the norms of the first step's clipped gradient, the program's
  read from its first moment as mu / (1 - b1), over the larger of the
  reference's norm of that leaf and of the median leaf;
* ``change_gap``: the same for the change of the parameters over the
  checked steps, leaving out the leaves whose reference gradient is under
  a thousandth of the median leaf's (they move by round-off alone);
* ``feed_mismatch``: rows the program was fed in the checked steps that
  differ from the traffic's own, plus rows that repeat (limit 0).

A number that is not finite fails its limit.
"""

from __future__ import annotations

import math

import numpy as np

MOVED = 1e-3        # a leaf moves if its reference gradient is above this
                    # share of the median leaf's


def _flat(norms: dict) -> dict[str, float]:
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            out.update((f"{name}[{i}]", float(x)) for i, x in enumerate(v))
    return out


def worst_gap(program: dict, reference: dict,
              keep: set[str] | None = None) -> float:
    """The largest gap of a leaf's norm between two norm trees."""
    p, r = _flat(program), _flat(reference)
    if set(p) != set(r):
        return math.inf
    names = [n for n in r if keep is None or n in keep]
    median = float(np.median([r[n] for n in names]))
    gaps = [abs(p[n] - r[n]) / max(r[n], median, 1e-30) for n in names]
    return max((g if g == g else math.inf for g in gaps), default=0.0)


def moved_leaves(ref_grad_norms: dict) -> set[str]:
    r = _flat(ref_grad_norms)
    median = float(np.median(list(r.values())))
    return {n for n, v in r.items() if v >= MOVED * median}


def feed_mismatch(fed: list[dict], expected: list[dict]) -> int:
    bad = 0
    seen = set()
    for got, want in zip(fed, expected):
        for k in ("tokens", "targets"):
            bad += int(np.sum(np.any(np.asarray(got[k]) != want[k],
                                     axis=-1)))
        for row in np.asarray(got["tokens"]):
            key = row.tobytes()
            bad += key in seen
            seen.add(key)
    return bad + abs(len(fed) - len(expected))


def numbers(program: dict, reference: dict, fed: list[dict],
            expected: list[dict]) -> dict[str, float]:
    lp, lr = program["losses"], reference["losses"]
    gaps = [abs(a - b) / abs(b) for a, b in zip(lp, lr)]
    loss_gap = (max(g if g == g else math.inf for g in gaps)   # NaN: inf
                if gaps and len(lp) == len(lr) else math.inf)
    grad_gap = worst_gap(program["grad_norms"], reference["grad_norms"])
    change_gap = worst_gap(program["change_norms"],
                           reference["change_norms"],
                           moved_leaves(reference["grad_norms"]))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "feed_mismatch": float(feed_mismatch(fed, expected))}


def judge(values: dict[str, float], limits: dict[str, float]
          ) -> tuple[bool, list[list]]:
    """(every number within its limit, [[name, value, limit], ...])."""
    rows = [[n, values.get(n, math.inf), limits[n]] for n in sorted(limits)]
    ok = all(v <= lim for _, v, lim in rows)       # NaN fails
    return ok, rows
