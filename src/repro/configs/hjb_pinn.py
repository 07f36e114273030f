"""The paper's own model: TT-compressed 3-layer sine MLP, problem-
parameterized over the ``repro.pde`` registry (PINNConfig rather than
ModelConfig — this is the photonic side).  The Table-1 rows below bind the
paper's 20-dim HJB benchmark; ``pinn_config``/``pinn_reduced`` build the
same model for any registered PDE (``--pde`` in ``repro.launch.train``)."""
import dataclasses

from repro.core.pinn import PINNConfig
from repro.core.photonic import NoiseModel

# paper Table 1 rows
ONN_OFFCHIP = PINNConfig(hidden=1024, mode="dense")
ONN_ONCHIP = PINNConfig(hidden=1024, mode="onn",
                        noise=NoiseModel(enabled=True))
TONN_OFFCHIP = PINNConfig(hidden=1024, mode="tt", tt_rank=2, tt_L=4)
TONN_ONCHIP = PINNConfig(hidden=1024, mode="tonn", tt_rank=2, tt_L=4,
                         noise=NoiseModel(enabled=True))

# the fused ZO hot path (DESIGN.md §Perf): incremental FD stencil + TT
# matvecs routed through the stacked Pallas kernel dispatcher
TONN_ONCHIP_FUSED = PINNConfig(hidden=1024, mode="tonn", tt_rank=2, tt_L=4,
                               deriv="fd_fast", use_fused_kernel=True,
                               noise=NoiseModel(enabled=True))

REDUCED = PINNConfig(hidden=64, mode="tt", tt_rank=2, tt_L=3)


def pinn_config(pde: str = "hjb-20d", mode: str = "tonn",
                fused: bool = True, noise: bool = False,
                **overrides) -> PINNConfig:
    """Paper-scale PINNConfig bound to a registry PDE.

    ``fused`` selects the multi-perturbation ZO hot path (incremental FD
    stencil + stacked TT contraction — DESIGN.md §Perf); ``noise`` enables
    the fabrication-noise model (photonic modes only).
    """
    base = PINNConfig(hidden=1024, mode=mode, tt_rank=2, tt_L=4, pde=pde,
                      deriv="fd_fast" if fused else "fd",
                      use_fused_kernel=fused,
                      noise=NoiseModel(enabled=noise))
    return dataclasses.replace(base, **overrides) if overrides else base


def pinn_reduced(pde: str = "hjb-20d", mode: str = "tt",
                 fused: bool = True, noise: bool = False,
                 **overrides) -> PINNConfig:
    """CI/CPU-sized variant of ``pinn_config`` (hidden 64, 3 TT cores)."""
    cfg = pinn_config(pde, mode, fused, noise, hidden=64, tt_L=3)
    return dataclasses.replace(cfg, **overrides) if overrides else cfg
