"""Share, in %, of the ``mesh_rect`` kernel's device time that the chip
would need at least: per call the larger of its FLOPs over peak FLOP/s and
its bytes over peak bandwidth (bench/counts_onn.py: 3 FLOP per wire per
level per feed column; trig tables, feed and output once), summed over one
step's calls on one chip, over the kernel's measured time per step.  The
body runs on the VPU, so against the MXU's peak it reads low."""

from _common import module, per_step_ms


def read(ctx):
    ms = per_step_ms(ctx, module("xtrace").kernel_match("mesh_rect"))
    if ms is None:
        return None
    least = module("counts_onn").rect_least_seconds(
        ctx["config"], ctx["sets_per_device"], ctx["peak"])
    return 100.0 * least / (ms * 1e-3)
