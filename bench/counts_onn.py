"""Operations and bytes of the ONN configuration ("ONN on-chip", Table 1),
from its shapes alone.

Every ONN layer is ``W = U Σ Vᵀ`` of two rectangular MZI meshes of the
hidden width; a P-port mesh has P levels.  Layer 0's input, 21 wide, is
zero-padded to its 1,024 ports, so only 21 columns of W_0 are read.  Two
ways to run a layer on R rows exist, and the counts of the whole step take
the cheaper for each layer, so that no later implementation can read above
peak:

  mesh on rows      both meshes applied to the R rows
  densify + product both meshes applied to the identity feed of the
                    columns that the input reads, then the R × cols × out
                    product

A mesh level updates every wire of every feed column with
``y = C·x + S·x_partner``: 3 FLOP per wire per level per column (4
multiplies and 2 adds per MZI, ``bench/counts.py``'s ``mesh_call``).  Bytes
are the least a call must move: its two trig tables (levels × ports), its
feed and its output, each once, in float32.

The ``mesh_rect`` body runs all four meshes.
"""

from __future__ import annotations

from counts import F32, mesh_call


def rect_call(ports: int, rows: int, sets: int, shared_feed: bool) -> dict:
    """One stacked ``mesh_rect`` call: ``sets`` rectangular meshes of
    ``ports`` ports (and levels) on ``rows`` feed columns each (shared by
    all sets when ``shared_feed``)."""
    levels = ports
    feed = rows * ports * (1 if shared_feed else sets)
    return {"flops": 3 * ports * levels * rows * sets,
            "bytes": F32 * (2 * levels * ports * sets + feed
                            + rows * ports * sets)}


def rect_calls_per_step(cfg: dict, sets: int) -> list:
    """The ``mesh_rect`` calls of one densification of the N + 1 parameter
    sets: per layer, Vᵀ on the shared identity feed of the columns its
    input reads (21 for layer 0, all for layer 1), then U on the
    result."""
    H, A = cfg["hidden"], cfg["space_dim"] + 1
    return [rect_call(H, A, sets, True), rect_call(H, A, sets, False),
            rect_call(H, H, sets, True), rect_call(H, H, sets, False)]


def rect_least_seconds(cfg: dict, sets: int, peak: dict) -> float:
    """Least device time of one step's ``mesh_rect`` calls: each call bound
    by the larger of FLOPs / peak FLOP/s and bytes / peak bandwidth."""
    return sum(max(c["flops"] / peak["flops_per_s"],
                   c["bytes"] / peak["bytes_per_s"])
               for c in rect_calls_per_step(cfg, sets))


def layer_flops(out_dim: int, ports: int, cols: int, rows: int) -> int:
    """One ONN layer of ``ports`` input ports, whose input of ``cols``
    entries is zero-padded to them, on ``rows`` rows: the cheaper of its
    two routes."""
    on_rows = (mesh_call(ports, rows, 1)["flops"]
               + mesh_call(out_dim, rows, 1)["flops"])
    densify = (mesh_call(ports, cols, 1)["flops"]
               + mesh_call(out_dim, cols, 1)["flops"]
               + 2 * rows * cols * out_dim)
    return min(on_rows, densify)


def step_flops(cfg: dict, job: dict, sets: int) -> int:
    """Model FLOPs of one ZO-signSGD step's stacked loss over ``sets``
    parameter sets (fd_fast stencil): layer 0 on the batch and the unit
    columns, layer 1 on the (2A + 1)·B stencil rows, the output head."""
    H, A, B = cfg["hidden"], cfg["space_dim"] + 1, job["batch"]
    rows = (2 * A + 1) * B
    per_set = (layer_flops(H, H, A, B + A) + layer_flops(H, H, H, rows)
               + 2 * H * rows)
    return per_set * sets
