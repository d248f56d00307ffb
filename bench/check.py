"""The comparison that decides ``correct``.

Every row that every sweep of the window produced is held against the
plain reference's row for the same (placement, affinity, ordering): the
lane's total bit transitions, its drain cycle and its flit count must be
equal. The comparison is exact, so each limit is 0. A row the reference
has and a sweep lacks, or the reverse, counts as missing.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

KEYS = ("placement", "affinity", "transform")
FIELDS = ("total_bt", "cycles", "flits")

# name -> limit; every number is a count or an absolute difference.
LIMITS = {"rows_missing": 0, "bt_rows_differing": 0, "bt_max_abs_diff": 0,
          "cycles_max_abs_diff": 0, "flits_max_abs_diff": 0}


def compare(sweeps: Sequence[List[Dict]], reference: List[Dict]):
    """``({name: {"value": v, "limit": l}}, rows due, rows wrong or
    missing)`` over all sweeps' rows."""
    ref = {tuple(r[k] for k in KEYS): r for r in reference}
    out = {k: 0 for k in LIMITS}
    wrong = 0
    for rows in sweeps:
        got = {tuple(r[k] for k in KEYS): r for r in rows}
        out["rows_missing"] += len(set(ref) ^ set(got))
        for key in set(ref) & set(got):
            a, b = got[key], ref[key]
            diff = {f: abs(int(a[f]) - int(b[f])) for f in FIELDS}
            wrong += int(any(diff.values()))
            out["bt_rows_differing"] += int(diff["total_bt"] > 0)
            out["bt_max_abs_diff"] = max(out["bt_max_abs_diff"],
                                         diff["total_bt"])
            out["cycles_max_abs_diff"] = max(out["cycles_max_abs_diff"],
                                             diff["cycles"])
            out["flits_max_abs_diff"] = max(out["flits_max_abs_diff"],
                                            diff["flits"])
    if not sweeps:
        out["rows_missing"] = len(ref)
    due = len(ref) * max(len(sweeps), 1)
    numbers = {k: {"value": v, "limit": LIMITS[k]} for k, v in out.items()}
    return numbers, due, wrong + out["rows_missing"]


def passed(numbers: Dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


def lines(numbers: Dict) -> List[str]:
    return [f"check {k}: {v['value']} (limit {v['limit']})"
            for k, v in numbers.items()]
