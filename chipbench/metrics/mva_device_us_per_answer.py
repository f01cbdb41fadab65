"""Device time of the batched MVA program per answer: the traced time of
``_mva_scan_batch`` over the answers."""

PROGRAM = "_mva_scan_batch"


def read(ctx):
    if ctx.summary is None:
        return None
    seconds = ctx.summary.program_seconds(PROGRAM)
    if seconds <= 0:
        return None
    return 1e6 * seconds / ctx.answers
