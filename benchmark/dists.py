"""Key distributions that a source's own generator draws from, in numpy.

``special`` is sysbench 1.0's ``sb_rand_special`` (``sb_rand.c``), the
default ``--rand-type``: of every ``100 / (100 - res)`` draws one is the
mean of ``iter`` uniform values over the range (a bell over the whole range)
and the others fall evenly on the ``pct`` % of the range around its middle.
With sysbench's defaults (``--rand-spec-iter=12 --rand-spec-pct=1
--rand-spec-res=75``) three quarters of the ids are among the 1% of the rows
in the middle of the table.  Whole-number arithmetic as the C has it.
"""

import numpy as np


def special(rng, lo: int, hi: int, size: int, iter: int = 12, pct: int = 1,
            res: int = 75) -> np.ndarray:
    """``size`` draws from [lo, hi], both ends included, as int64."""
    t = hi - lo + 1
    mult = 100 // (100 - res)
    pick = rng.integers(0, t * mult, size)
    bell = rng.integers(0, t, (size, iter)).sum(axis=1) // iter
    d = max(1, t * pct // 100)
    few = pick % d + (t // 2 - t * pct // 200)
    return lo + np.where(pick < t, bell, few).astype(np.int64)
