"""Share of the execute scan's lane-steps that come after a lane's last
completion: 100 x (1 - lane_steps / scan_lane_steps), from the program's
counters of each answer (``repro.execute.lane_steps``, the steps each
lane needed; ``repro.execute.scan_lane_steps``, lanes x scan steps)."""

from chipbench.program_spans import per_answer

LANE_STEPS = "repro.execute.lane_steps"
SCAN_LANE_STEPS = "repro.execute.scan_lane_steps"


def read(ctx):
    scan = per_answer(ctx, lambda r: r.counts.get(SCAN_LANE_STEPS, 0))
    if not scan:
        return None
    lane = per_answer(ctx, lambda r: r.counts.get(LANE_STEPS, 0))
    return 100.0 * (1.0 - lane / scan)
