"""Share, in %, of the pool's point slots that carried request points:
``points_served`` over ``program_runs`` × slots × slot points, from the
engine's counters."""


def read(ctx):
    stats = ctx.get("counters")
    if not stats or not stats["program_runs"]:
        return None
    mix = ctx["mix"]
    pool = stats["program_runs"] * mix["slots"] * mix["slot_points"]
    return 100.0 * stats["points_served"] / pool
