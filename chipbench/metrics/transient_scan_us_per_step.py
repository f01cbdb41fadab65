"""Device time of the token scan program per scan step: the traced time
of ``_transient_batch`` over the answers times their steps."""

PROGRAM = "_transient_batch"


def read(ctx):
    if ctx.summary is None:
        return None
    seconds = ctx.summary.program_seconds(PROGRAM)
    if seconds <= 0:
        return None
    return 1e6 * seconds / (ctx.answers * ctx.engine.steps_per_answer)
