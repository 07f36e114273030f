"""Share, in %, of the ``tt_contract`` kernel's device time that the chip
would need at least: per call the larger of its FLOPs over peak FLOP/s and
its bytes over peak bandwidth (bench/counts.py, factorized TT count, the
activations in and out and the cores once), summed over one step's calls
on one chip, over the kernel's measured time per step."""

from _common import module, per_step_ms


def read(ctx):
    ms = per_step_ms(ctx, module("xtrace").kernel_match("tt_contract"))
    if ms is None:
        return None
    least = module("counts").tt_least_seconds(
        ctx["config"], ctx["mix"], ctx["sets_per_device"], ctx["peak"])
    return 100.0 * least["seconds"] / (ms * 1e-3)
