"""Plain references: the answers the served statements have to give.

A reference is a :class:`Ref`: ``answer(ctx, params, lower)`` computes the
statement's answer with numpy over the Arrow tables the loader generated
(``ctx["tables"]``; ``ctx`` is also the reference's own cache), and
``gaps(columns, rows, want)`` compares rows as the wire returned them with
that answer, giving one number per name in the cell's ``limits``.  With
``lower`` the answer is computed in the nearest precision below the one the
configuration states: that is the control, which the limits have to fail.
References import nothing of the program.
"""

from dataclasses import dataclass
from typing import Callable


@dataclass(frozen=True)
class Ref:
    answer: Callable    # (ctx, params, lower=False) -> (columns, rows)
    gaps: Callable      # (columns, rows, want) -> {name: number}
