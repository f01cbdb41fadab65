"""Host seconds an execute answer spends probing the real cluster: the
self time of its ``repro.execute.probe`` spans."""

from chipbench.program_spans import per_answer

SPAN = "repro.execute.probe"


def read(ctx):
    return per_answer(ctx, lambda r: r.self_s(SPAN))
