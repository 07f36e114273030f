"""Plain float32 reference of the paper's ONN HJB PINN ("ONN on-chip",
Table 1) and its ZO-signSGD step.

Written from the paper (arXiv:2401.00413 §2-§4), Clements et al. (Optica
2016) for the mesh, and the configuration file alone; it imports nothing
of the program under test.  Weights, fabrication noise, collocation points
and SPSA perturbations are drawn from the seed with the same
``jax.random`` calls the configuration states, so reference and program
start from the same numbers without sharing any code.

Model (one parameter set):
  W_l = U(Φ_u) diag(σ) V(Φ_v)ᵀ             every layer an SVD of two meshes
  U, V                                     rectangular MZI meshes: a P-port
                                           mesh has P columns; column c
                                           rotates wires (a, a+1), a ≡ c mod 2
  Φ_eff = Ω(Γ ⊙ Φ) + Φ_b                   fabrication noise (gamma, crosstalk, bias)
  f(x) = w2 · sin(W_1 sin(W_0 x̂ + b_0) + b_1) + b_2,  x̂ = x zero-padded
  u(x, t) = (1 − t) f + ‖x‖₁               HJB ansatz (terminal condition exact)
Both layers are 1024 × 1024: two 1024-port meshes and 1,024 singular values
each, 2,095,104 MZIs in all.  The input (x, t) has 21 entries and is
zero-padded to the 1,024 ports of layer 0, so only W_0's first 21 columns
are ever read, and only those are formed (W_0 e_j, j < 21).
Loss: mean over the batch of the squared HJB residual
  u_t + Δu − λ‖∇u‖² + 2,  derivatives by central differences at step h,
with layer 0 evaluated once per point and shifted by ±h·W_0[:, i] (the
configuration's ``fd_fast`` stencil: layer 0 is linear, so this is exact).

Departures from the paper, each stated by the configuration:
  * the meshes are real: each MZI is the rotation R(φ) = [[cos φ, −sin φ],
    [sin φ, cos φ]], as the paper's rotators are;
  * each mesh carries a fixed ±1 diagonal (all ones at initialization),
    which the configuration does not train;
  * the fabrication-noise magnitudes are the configuration's;
  * a rectangular level of wires with no partner passes them unchanged,
    and the crosstalk Ω couples neighbouring phase slots of a column,
    the unused last slot of odd columns included.

Every matrix product goes through ``dot(a, b, precision)``: ``"highest"``
is full float32; ``"high"`` is the three-pass bfloat16 product
(hi·hi + hi·lo + lo·hi), emulated explicitly so that it means the same on
every backend.  The control of the benchmark's comparison runs this
reference at ``"high"``; the ZO steps run under
``jax.default_matmul_precision("highest")`` besides.  The mesh columns are
looped with ``lax.fori_loop``, so a 1,024-column mesh compiles and runs in
seconds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def bf16_round(x):
    """x rounded to bfloat16 (nearest, ties to even), kept in float32.
    Done on the bits: a compiler that may keep excess precision (XLA:CPU)
    can drop an f32 → bf16 → f32 round trip of ``astype``."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + (((bits >> 16) & 1) + jnp.uint32(0x7FFF))
    bits = bits & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def dot(a, b, precision: str):
    """a @ b (last axis of a against first of b) in the stated precision."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=_HIGHEST)
    if precision == "high":
        def split(x):
            hi = bf16_round(x)
            return hi, bf16_round(x - hi)
        ah, al = split(a)
        bh, bl = split(b)
        mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


# ------------------------------------------------------------------ shapes

def layer_dims(cfg: dict) -> list:
    """(out, in) of the two mesh layers: the input is zero-padded to the
    hidden width."""
    H = cfg["hidden"]
    return [(H, H), (H, H)]


def layer_columns(cfg: dict) -> list:
    """Columns of each layer's W that its input reads: the unpadded input
    width for layer 0, all of them for layer 1."""
    return [cfg["space_dim"] + 1, cfg["hidden"]]


def phase_shape(ports: int) -> tuple:
    """(columns, MZIs in the fullest column) of a rectangular mesh: column c
    holds (ports − c mod 2) // 2 MZIs; columns without one are dropped."""
    cols = [(ports - c % 2) // 2 for c in range(ports)]
    cols = [n for n in cols if n]
    return (len(cols), max(cols))


# -------------------------------------------------------------------- init

def init_params(cfg: dict, key) -> dict:
    """Seeded initial parameters: random phases, glorot-scaled singular
    values, ±1 diagonals at one."""
    H = cfg["hidden"]
    keys = jax.random.split(key, 8)
    params = {}
    for i, (out_dim, in_dim) in enumerate(layer_dims(cfg)):
        params[f"p{i}"] = _init_mesh_matrix(keys[i], out_dim, in_dim)
        params[f"b{i}"] = jnp.zeros((out_dim,))
    params["w2"] = (math.sqrt(2.0 / (1 + H))
                    * jax.random.normal(keys[6], (1, H)))
    params["b2"] = jnp.zeros((1,))
    return params


def _init_mesh_matrix(key, out_dim: int, in_dim: int) -> dict:
    ku, kv, ks = jax.random.split(key, 3)
    k = min(out_dim, in_dim)
    std = math.sqrt(2.0 / (in_dim + out_dim))
    return {
        "phases_u": 0.1 * jax.random.normal(ku, phase_shape(out_dim)),
        "phases_v": 0.1 * jax.random.normal(kv, phase_shape(in_dim)),
        "sigma": std * math.sqrt(float(k)) * jnp.abs(
            1.0 + 0.1 * jax.random.normal(ks, (k,))),
        "diag_u": jnp.ones((out_dim,), dtype=jnp.float32),
        "diag_v": jnp.ones((in_dim,), dtype=jnp.float32),
    }


def sample_noise(cfg: dict, key) -> dict | None:
    """Per-chip fabrication noise, drawn once from fold_in(seed key, 99)."""
    nz = cfg["noise"]
    if not nz["enabled"]:
        return None
    dims = layer_dims(cfg)
    keys = jax.random.split(key, len(dims))
    out = {}
    for i, (out_dim, in_dim) in enumerate(dims):
        ku, kv = jax.random.split(keys[i])
        out[f"p{i}"] = {"u": _noise(nz, ku, phase_shape(out_dim)),
                        "v": _noise(nz, kv, phase_shape(in_dim))}
    return out


def _noise(nz: dict, key, shape) -> dict:
    k1, k2 = jax.random.split(key)
    gamma = nz["gamma_mean"] + nz["gamma_std"] * jax.random.normal(k1, shape)
    bias = nz["phase_bias_scale"] * jax.random.uniform(
        k2, shape, minval=0.0, maxval=2.0 * math.pi)
    return {"gamma": gamma.astype(jnp.float32),
            "bias": bias.astype(jnp.float32)}


def is_buffer_path(path) -> bool:
    """The ±1 diagonals pin each mesh to its orthogonal decomposition; the
    configuration does not train them."""
    return any(getattr(k, "key", None) in ("diag_u", "diag_v") for k in path)


# ------------------------------------------------------------ dense weights

def effective_phases(nz: dict, phases, noise):
    """Φ_eff = Ω(Γ ⊙ Φ) + Φ_b, Ω coupling neighbouring MZIs of a column."""
    p = noise["gamma"] * phases
    c = nz["crosstalk"]
    left = jnp.pad(p[..., 1:], [(0, 0), (0, 1)])
    right = jnp.pad(p[..., :-1], [(0, 0), (1, 0)])
    return p + c * (left + right) + noise["bias"]


def _rotate_column(x, phases_c, parity: int, sign: float):
    """One mesh column on x (B, P): wires (a, a+1), a = parity, parity + 2,
    …, rotated by R(sign · φ_k) for the k-th pair, written per wire: its
    own amplitude times cos φ plus its partner's times ∓sin φ."""
    P = x.shape[-1]
    n = (P - parity) // 2
    if n == 0:
        return x
    seg = x[:, parity:parity + 2 * n].reshape(x.shape[0], n, 2)
    xa, xb = seg[..., 0], seg[..., 1]
    ph = phases_c[:n]
    cos, sin = jnp.cos(ph), sign * jnp.sin(ph)
    rot = jnp.stack([cos * xa + (-sin) * xb, cos * xb + sin * xa], axis=-1)
    return jnp.concatenate([x[:, :parity], rot.reshape(x.shape[0], 2 * n),
                            x[:, parity + 2 * n:]], axis=-1)


def mesh_apply(phases, diag, x, transpose: bool):
    """Rotate the columns of x (B, P) through the mesh, one MZI column at a
    time.  The mesh is U = R_C ⋯ R_1 D; ``transpose`` applies
    Uᵀ = D R_1ᵀ ⋯ R_Cᵀ (columns backwards, angles negated).  Column c has
    parity c mod 2; the loop runs over pairs of columns."""
    C = phases.shape[0]
    if transpose:
        phases = phases[::-1]
        first = (C - 1) % 2
        sign = -1.0
    else:
        x = x * diag
        first = 0
        sign = 1.0

    def two_columns(j, x):
        x = _rotate_column(x, phases[2 * j], first, sign)
        return _rotate_column(x, phases[2 * j + 1], 1 - first, sign)

    x = jax.lax.fori_loop(0, C // 2, two_columns, x)
    if C % 2:
        x = _rotate_column(x, phases[C - 1], (first + C - 1) % 2, sign)
    if transpose:
        x = x * diag
    return x


def mesh_matrix_dense(pm: dict, noise: dict | None, nz: dict, out_dim: int,
                      in_dim: int, cols: int):
    """The first ``cols`` columns W e_j of W = U Σ Vᵀ, one MZI-mesh matrix
    (out_dim × cols)."""
    pu, pv = pm["phases_u"], pm["phases_v"]
    if noise is not None:
        pu = effective_phases(nz, pu, noise["u"])
        pv = effective_phases(nz, pv, noise["v"])
    k = min(out_dim, in_dim)
    z = mesh_apply(pv, pm["diag_v"],
                   jnp.eye(cols, in_dim, dtype=jnp.float32), transpose=True)
    z = z[:, :k] * pm["sigma"]
    z = jnp.pad(z, [(0, 0), (0, out_dim - k)])
    return mesh_apply(pu, pm["diag_u"], z, transpose=False).T


def dense_weights(params: dict, noise: dict | None, cfg: dict) -> tuple:
    """(W_0, W_1) of one parameter set, W_0 as the columns its input
    reads."""
    out = []
    for i, ((out_dim, in_dim), cols) in enumerate(
            zip(layer_dims(cfg), layer_columns(cfg))):
        nzi = None if noise is None else noise[f"p{i}"]
        out.append(mesh_matrix_dense(params[f"p{i}"], nzi, cfg["noise"],
                                     out_dim, in_dim, cols))
    return tuple(out)


# ----------------------------------------------------------------- forward

def ansatz(f, pts, space_dim: int):
    x, t = pts[..., :space_dim], pts[..., space_dim]
    return (1.0 - t) * f + jnp.sum(jnp.abs(x), axis=-1)


def stencil_u(params: dict, noise: dict | None, xt, cfg: dict,
              precision: str):
    """u at the central-difference stencil [x, x + h·e_1, …, x − h·e_A] of
    every collocation point: (2A + 1, B).  Layer 1 and the head run on the
    stencil's (2A + 1)·B rows as one matrix."""
    D = cfg["space_dim"]
    A = D + 1
    h = cfg["fd_step"]
    B, H = xt.shape[0], cfg["hidden"]
    w0, w1 = dense_weights(params, noise, cfg)
    z0 = dot(xt, w0.T, precision) + params["b0"]                 # (B, H)
    hcols = h * w0.T                                              # (A, H)
    z = jnp.concatenate([z0[None], z0[None] + hcols[:, None],
                         z0[None] - hcols[:, None]], axis=0)      # (2A+1,B,H)
    z1 = dot(jnp.sin(z).reshape(-1, H), w1.T, precision) + params["b1"]
    f = jnp.sum(jnp.sin(z1) * params["w2"], axis=-1) + params["b2"][0]
    f = f.reshape(2 * A + 1, B)
    e = jnp.eye(A, A, dtype=xt.dtype) * jnp.asarray(h, dtype=xt.dtype)
    pts = jnp.concatenate([xt[None], xt[None] + e[:, None, :],
                           xt[None] - e[:, None, :]], axis=0)
    return ansatz(f, pts, D)


def residual_loss(params: dict, noise: dict | None, xt, cfg: dict,
                  precision: str, rows=None):
    """Mean squared HJB residual over the collocation batch ``xt``;
    ``rows`` limits the mean to a slice of the batch."""
    D = cfg["space_dim"]
    A = D + 1
    h = cfg["fd_step"]
    lam = 1.0 / D
    u = stencil_u(params, noise, xt, cfg, precision)
    u0, up, um = u[0], u[1:A + 1], u[A + 1:]
    grad = ((up - um) / (2.0 * h)).T
    hess = ((up - 2.0 * u0[None] + um) / (h * h)).T
    r = (grad[:, D] + jnp.sum(hess[:, :D], axis=-1)
         - lam * jnp.sum(grad[:, :D] * grad[:, :D], axis=-1) + 2.0)
    if rows is not None:
        r = r[rows]
    return jnp.mean(r * r)


# -------------------------------------------------------------- data, ZO step

def collocation(cfg: dict, seed: int, step: int, batch: int):
    """Uniform (x, t) in [margin, 1 − margin]^(D+1), counter-keyed."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                step), 0)
    m = cfg["collocation_margin"]
    return jax.random.uniform(key, (batch, cfg["space_dim"] + 1),
                              minval=m, maxval=1.0 - m)


def perturbations(key, params: dict, n: int) -> list:
    """ξ_1..ξ_N ~ N(0, I) over the trainable leaves (zero on the buffers):
    key i of split(key, n), one subkey per leaf in flattening order."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for ki in jax.random.split(key, n):
        lk = jax.random.split(ki, len(flat))
        out.append(jax.tree.unflatten(treedef, [
            jnp.zeros_like(leaf) if is_buffer_path(path)
            else jax.random.normal(k, leaf.shape, dtype=leaf.dtype)
            for k, (path, leaf) in zip(lk, flat)]))
    return out


def seeded_start(cfg: dict, seed: int) -> tuple:
    """(params, noise) of the seed, each drawn in one jitted call."""
    key = jax.random.PRNGKey(seed)
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    noise = jax.jit(lambda k: sample_noise(cfg, k))(
        jax.random.fold_in(key, 99))
    return params, noise


def zo_signsgd_steps(cfg: dict, job: dict, seed: int, steps: int,
                     precision: str = "highest", half_batch: bool = False
                     ) -> dict:
    """``steps`` ZO-signSGD updates (paper Eqs. 5-6) from the seeded start.

    Returns the base loss of each step, the initial parameters, those
    after step 1 and after the last step, the first step's SPSA gradient
    and its N + 1 losses.  ``half_batch`` plants a fault for the
    calibration: the loss over half of the batch."""
    params, noise = seeded_start(cfg, seed)
    n, mu, lr = job["zo_samples"], job["mu"], job["lr"]
    rows = slice(0, job["batch"] // 2) if half_batch else None

    @jax.jit
    def step(params, noise, zkey, xt):
        loss = lambda p: residual_loss(p, noise, xt, cfg, precision, rows)
        zkey, sub = jax.random.split(zkey)
        xis = perturbations(sub, params, n)
        stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *xis)
        base = loss(params)
        ls = jax.lax.map(lambda xi: loss(jax.tree.map(
            lambda p, z: p + mu * z, params, xi)), stacked)
        coefs = (ls - base) / (n * mu)
        grad = jax.tree.map(lambda z: jnp.tensordot(coefs, z, axes=1),
                            stacked)
        new = jax.tree.map(lambda p, g: p - jnp.float32(lr) * jnp.sign(g),
                           params, grad)
        return new, zkey, base, grad, jnp.concatenate([base[None], ls])

    zkey = jax.random.PRNGKey(seed + 1)
    out = {"loss": [], "p0": params}
    with jax.default_matmul_precision("highest"):
        for s in range(steps):
            xt = collocation(cfg, seed, s, job["batch"])
            params, zkey, base, grad, stack = step(params, noise, zkey, xt)
            out["loss"].append(float(base))
            if s == 0:
                out["p1"], out["grad0"] = params, grad
                out["stack0"] = [float(x) for x in stack]
    out["p_last"] = params
    return out
