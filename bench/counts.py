"""Operations and bytes that the configuration's algorithm requires, from
its shapes alone.

The counts do not depend on how a layer is implemented.  A TT layer counts
as its factorized core contraction (the chain of small matmuls of the
paper's §2.1), never as the densified M × N matrix that one kernel happens
to build: a later kernel that contracts the cores directly must not read
above peak.  Bytes are the least a call must move: its activations in and
out and its cores (or phases), each once, in float32.

One ZO-signSGD step of the stacked path evaluates P = N + 1 parameter sets
(base + N perturbations) on a shared collocation batch of B points with the
``fd_fast`` stencil (2A + 1 rows per point, A = in_dim):

  layer 0   TT on the B points, and on the A unit columns (the stencil's
            rank-1 shifts), per parameter set
  layer 1   TT on the (2A + 1)·B stencil rows, per parameter set
  head      a hidden-wide multiply-add per stencil row
  meshes    (tonn) each core's two MZI meshes applied to an identity feed
"""

from __future__ import annotations

import numpy as np

F32 = 4


def tt_call(cfg: dict, rows: int, sets: int, shared_x: bool) -> dict:
    """One stacked TT-linear call: ``sets`` core sets on ``rows`` rows each
    (the rows shared by all sets when ``shared_x``)."""
    m, n, r = cfg["tt_out_modes"], cfg["tt_in_modes"], cfg["tt_ranks"]
    M, N = int(np.prod(m)), int(np.prod(n))
    macs, m_prefix, n_suffix = 0, 1, N
    for k in range(len(m)):
        n_suffix //= n[k]
        macs += m_prefix * n_suffix * (r[k] * n[k]) * (m[k] * r[k + 1])
        m_prefix *= m[k]
    core_elems = sum(r[k] * m[k] * n[k] * r[k + 1] for k in range(len(m)))
    x_elems = rows * N * (1 if shared_x else sets)
    return {"flops": 2 * macs * rows * sets,
            "bytes": F32 * (x_elems + rows * M * sets + core_elems * sets)}


def tt_calls_per_step(cfg: dict, job: dict, sets: int) -> list:
    """The TT-linear calls of one stacked loss evaluation, with the operand
    shapes the kernel sees: (name, rows, shared_x)."""
    A = cfg["space_dim"] + 1
    B = job["batch"]
    return [("layer0", B, True), ("layer0_columns", A, True),
            ("layer1", (2 * A + 1) * B, False)]


def rectangular_mzis(ports: int) -> int:
    return ports * (ports - 1) // 2


def mesh_call(ports: int, rows: int, sets: int) -> dict:
    """One stacked mesh application: each MZI rotates a wire pair of every
    row (4 multiplies + 2 adds), per parameter set."""
    mzis = rectangular_mzis(ports)
    return {"flops": 6 * mzis * rows * sets,
            "bytes": F32 * sets * (2 * rows * ports + mzis)}


def mesh_calls_per_step(cfg: dict, sets: int) -> list:
    """The mesh applications of one TONN densification: per layer and core,
    V on an identity feed, then U on the scaled result."""
    if cfg["mode"] != "tonn":
        return []
    r, m, n = cfg["tt_ranks"], cfg["tt_out_modes"], cfg["tt_in_modes"]
    calls = []
    for _layer in range(2):
        for k in range(len(m)):
            out_p, in_p = r[k] * m[k], n[k] * r[k + 1]
            calls.append(mesh_call(in_p, in_p, sets))
            calls.append(mesh_call(out_p, in_p, sets))
    return calls


def step_flops(cfg: dict, job: dict, sets: int) -> int:
    """Model FLOPs of one ZO-signSGD step's stacked loss over ``sets``
    parameter sets: TT contractions, output head and mesh densification."""
    A = cfg["space_dim"] + 1
    stencil_rows = (2 * A + 1) * job["batch"]
    total = sum(tt_call(cfg, rows, sets, shared)["flops"]
                for _, rows, shared in tt_calls_per_step(cfg, job, sets))
    total += 2 * cfg["hidden"] * stencil_rows * sets
    total += sum(c["flops"] for c in mesh_calls_per_step(cfg, sets))
    return total


def tt_least_seconds(cfg: dict, job: dict, sets: int, peak: dict) -> dict:
    """Least device time of one step's TT-linear calls: each call bound by
    the larger of FLOPs / peak FLOP/s and bytes / peak bandwidth."""
    out = {"seconds": 0.0, "compute_s": 0.0, "memory_s": 0.0}
    for _, rows, shared in tt_calls_per_step(cfg, job, sets):
        c = tt_call(cfg, rows, sets, shared)
        tc = c["flops"] / peak["flops_per_s"]
        tm = c["bytes"] / peak["bytes_per_s"]
        out["seconds"] += max(tc, tm)
        out["compute_s"] += tc
        out["memory_s"] += tm
    out["bound"] = "memory" if out["memory_s"] >= out["compute_s"] \
        else "compute"
    return out
