"""Host seconds of an answer inside the program: its root span's duration
less the spans that wait on the device (``repro.<engine>.wait``)."""

from chipbench.program_spans import per_answer


def read(ctx):
    return per_answer(ctx, lambda r: r.duration_s - r.self_s(r.name + ".wait"))
