"""Bytes an answer copies from the device to the host, as the program
counts them (``repro.pull_bytes``)."""

from chipbench.program_spans import per_answer

COUNTER = "repro.pull_bytes"


def read(ctx):
    return per_answer(ctx, lambda r: r.counts.get(COUNTER, 0))
