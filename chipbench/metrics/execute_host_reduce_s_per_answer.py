"""Host seconds an execute answer spends pulling its results and reducing
them: the self time of ``repro.execute.pull`` and ``repro.execute.reduce``
(the float64 latency sums, messages and quantiles)."""

from chipbench.program_spans import per_answer

SPANS = ("repro.execute.pull", "repro.execute.reduce")


def read(ctx):
    return per_answer(ctx, lambda r: sum(r.self_s(s) for s in SPANS))
