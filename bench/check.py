"""The comparison that decides `correct`.

For each sampled request the timed window answered, the plain reference of
the configuration gives, for every query row, its exact top-k under
(count desc, id asc) and its own count of every id the program returned.
Two numbers are compared, each against the limit the configuration's file
states:

  count_mismatch  share of returned (row, rank) slots whose count is not the
                  reference's count of the returned id: a wrong id, a wrong
                  count, or signatures hashed differently;
  rank_mismatch   share of returned slots whose (id, count) is not the
                  reference's at that rank: a missed candidate, a broken
                  merge, a wrong tie order, or rows sliced to the wrong
                  request.

A request that failed or never came counts as `unanswered`, whose limit is 0.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("count_mismatch", "rank_mismatch")


def compare(served_ids: np.ndarray, served_counts: np.ndarray,
            ref_ids: np.ndarray, ref_counts: np.ndarray,
            recount: np.ndarray) -> dict:
    """The compared numbers over [rows, k] arrays of the sampled requests."""
    slots = served_ids.size
    if not slots:
        raise ValueError("no served slot to compare")
    return {
        "count_mismatch": float(np.sum(served_counts != recount)) / slots,
        "rank_mismatch": float(np.sum((served_ids != ref_ids)
                                      | (served_counts != ref_counts))) / slots,
    }


def judge(numbers: dict, limits: dict) -> bool:
    """True when every number is at or under its limit."""
    return all(numbers[k] <= limits[k] for k in numbers)


def lines(numbers: dict, limits: dict) -> list[str]:
    return [f"check {k}: {numbers[k]:.6g} (limit {limits[k]:.6g})"
            for k in numbers]
