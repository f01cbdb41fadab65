"""Probe runs of the real cluster per execute answer, as the program
counts them (``repro.execute.probe_runs``)."""

from chipbench.program_spans import per_answer

COUNTER = "repro.execute.probe_runs"


def read(ctx):
    return per_answer(ctx, lambda r: r.counts.get(COUNTER, 0))
