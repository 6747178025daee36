"""Plain reference for the validation pass: the paper's quorum check (§3.4,
§4) with a fuzzy comparator, applied pair by pair in NumPy.

It shares no code with the program. A job's reported results are visited
in the order they were created; each joins the first group whose first
member it agrees with, else it opens a group. Two results agree when every
element satisfies ``|rep - x| <= atol + rtol * |x|``. The largest group
(earliest on a tie) wins if it holds at least ``min_quorum`` results: its
first member is canonical, its members are ``valid`` and the rest
``invalid``. Otherwise every result is ``inconclusive``.

``dtype`` is the precision the payloads are compared in: float64 is the
reference, and a lower precision (the payloads rounded to it first) is the
control that the comparison has to reject.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np


def agree(rep: np.ndarray, x: np.ndarray, rtol: float, atol: float, dtype=np.float64,
          chunk: int = 1 << 18) -> bool:
    """Whether every element agrees, checked a chunk at a time (a corrupt
    result fails in its first chunk)."""
    for i in range(0, len(x), chunk):
        a = rep[i:i + chunk].astype(dtype).astype(np.float64)
        b = x[i:i + chunk].astype(dtype).astype(np.float64)
        if not np.all(np.abs(a - b) <= atol + rtol * np.abs(b)):
            return False
    return True


def verdict(results: Sequence[np.ndarray], rtol: float, atol: float, min_quorum: int,
            dtype=np.float64) -> Tuple[List[str], Optional[int]]:
    """(state per result, index of the canonical result or None)."""
    if len(results) < min_quorum:
        return ["init"] * len(results), None
    groups: List[List[int]] = []
    for i, x in enumerate(results):
        for g in groups:
            if agree(results[g[0]], x, rtol, atol, dtype):
                g.append(i)
                break
        else:
            groups.append([i])
    best = max(groups, key=len)  # max keeps the earliest of equal lengths
    if len(best) < min_quorum:
        return ["inconclusive"] * len(results), None
    states = ["invalid"] * len(results)
    for i in best:
        states[i] = "valid"
    return states, best[0]
