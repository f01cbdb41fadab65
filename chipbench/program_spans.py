"""The program's own record of the window's answers, for the readers of
``program_span`` and ``program_counter`` metrics.

Each engine entry of the program opens one root span per answer,
``repro.<engine>`` (``repro.core.tracing``), holding the self time of
each child span (``repro.<engine>.lower``, ``.probe``, ``.wait``,
``.pull``, ...) and the answer's counters.  The check calls no engine, so
the last roots are the window's answers.  A program without that record
gives nothing to read.
"""
from __future__ import annotations

from typing import Callable, Optional


def per_answer(ctx, value: Callable) -> Optional[float]:
    """Mean of ``value(root)`` over the window's answers, or ``None``
    where the program recorded fewer."""
    try:
        from repro.core import tracing
    except ImportError:
        return None
    name = "repro." + ctx.engine.traffic["engine"]
    roots = tracing.recent(name, ctx.answers)
    if ctx.answers <= 0 or len(roots) < ctx.answers:
        return None
    return sum(float(value(r)) for r in roots) / len(roots)
