"""Device ms per step in the ``mesh_apply_stacked`` Pallas kernel
(kernels/mesh_apply.py): TONN densification of the MZI core meshes."""

from _common import module, per_step_ms


def read(ctx):
    return per_step_ms(ctx, module("xtrace").kernel_match("mesh_apply_stacked"))
