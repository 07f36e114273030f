"""Device ms per step in the ``mesh_rect`` Pallas kernel
(kernels/mesh_apply.py): densification of the hidden-wide ONN meshes."""

from _common import module, per_step_ms


def read(ctx):
    return per_step_ms(ctx, module("xtrace").kernel_match("mesh_rect"))
