"""Device ms per step in the XLA ops of the ONN step: everything that is
neither one of the Pallas kernels nor a collective (the dense layer
products, stencil assembly, sin, the output head, SPSA sampling and the
sign update)."""

from _common import module, per_step_ms

KERNELS = ("tt_contract", "mesh_apply_stacked", "mesh_rect")


def read(ctx):
    xtrace = module("xtrace")
    kernels = [xtrace.kernel_match(k) for k in KERNELS]
    return per_step_ms(ctx, lambda n: not xtrace.is_collective(n)
                       and not any(k(n) for k in kernels))
