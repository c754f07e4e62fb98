"""The comparisons that decide ``correct``.

``compare`` takes per-key window results: each one the sink received is
looked up in the reference's exact totals, and every result that was due
(its window end at or before the due horizon, with a nonzero reference
total) has to be there, once.  ``compare_top`` takes one answer per
window end, the key with the highest total.  The counts are exact, so
each number compared has the limit 0.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

#: number compared -> its limit (exact comparison: none may be off)
LIMITS = {"wrong_values": 0, "missing": 0, "extra": 0}


def compare(cols: Dict[str, np.ndarray], totals: np.ndarray, slide_ms: int,
            due_end: int) -> Dict[str, int]:
    """``cols``: received ``end``/``key``/``value`` columns; ``totals``: the
    reference, row ``i`` for the window ending at ``(i + 1) * slide_ms``.

    * ``wrong_values``: results whose value differs from the reference;
    * ``missing``: due results never received;
    * ``extra``: results the reference does not have (a window end off
      the slide grid, a key out of range, a zero total, a duplicate).
    """
    end, key, value = cols["end"], cols["key"], cols["value"]
    n_ends, n_keys = totals.shape
    idx = end // slide_ms - 1
    ok = ((end % slide_ms == 0) & (idx >= 0) & (idx < n_ends)
          & (key >= 0) & (key < n_keys))
    flat = np.where(ok, idx * n_keys + key, -1)
    expect = np.where(ok, totals.reshape(-1)[np.maximum(flat, 0)], 0)
    ok &= expect != 0
    uniq = np.unique(flat[ok])
    duplicates = int(ok.sum()) - len(uniq)
    wrong = int(np.count_nonzero(value[ok] != expect[ok]))
    n_due = min(max(due_end // slide_ms, 0), n_ends)
    due = totals[:n_due].reshape(-1) != 0
    got = np.zeros(n_due * n_keys, bool)
    got[uniq[uniq < n_due * n_keys]] = True
    return {"wrong_values": wrong,
            "missing": int(np.count_nonzero(due & ~got)),
            "extra": int(np.count_nonzero(~ok)) + duplicates}


def compare_top(cols: Dict[str, np.ndarray], totals: np.ndarray,
                slide_ms: int, due_end: int) -> Dict[str, int]:
    """``cols``: received answers, one per window end: the window's
    highest total and a key that has it; ``totals`` as for ``compare``.

    * ``wrong_values``: answers whose value is not the window's highest
      total, or whose key does not have that total;
    * ``missing``: due window ends (with a nonzero total) never answered;
    * ``extra``: answers the reference does not have (a window end off
      the slide grid or with no total, a second answer for a window end).
    """
    end, key, value = cols["end"], cols["key"], cols["value"]
    n_ends, n_keys = totals.shape
    idx = end // slide_ms - 1
    top = totals.max(axis=1, initial=0)
    ok = (end % slide_ms == 0) & (idx >= 0) & (idx < n_ends)
    row = np.where(ok, idx, 0)
    ok &= top[row] != 0
    in_range = (key >= 0) & (key < n_keys)
    holds = np.zeros(len(end), bool)
    holds[in_range] = totals[row[in_range], key[in_range]] == top[row[in_range]]
    answered = np.unique(idx[ok])
    wrong = int(np.count_nonzero(ok & ((value != top[row]) | ~holds)))
    n_due = min(max(due_end // slide_ms, 0), n_ends)
    due = top[:n_due] != 0
    got = np.zeros(n_due, bool)
    got[answered[answered < n_due]] = True
    return {"wrong_values": wrong,
            "missing": int(np.count_nonzero(due & ~got)),
            "extra": int(np.count_nonzero(~ok))
            + int(ok.sum()) - len(answered)}


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
