"""Device ms per step in the ``tt_contract`` Pallas kernel
(kernels/tt_contract.py), summed over its calls of the step."""

from _common import module, per_step_ms


def read(ctx):
    return per_step_ms(ctx, module("xtrace").kernel_match("tt_contract"))
