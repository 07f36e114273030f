"""Plain float32 reference of the paper's TONN HJB PINN and its ZO-signSGD
step.

Written from the paper (arXiv:2401.00413 §2-§4) and the configuration file
alone; it imports nothing of the program under test.  Weights, fabrication
noise, collocation points and SPSA perturbations are drawn from the seed
with the same ``jax.random`` calls the configuration states, so reference
and program start from the same numbers without sharing any code.

Model (one parameter set):
  W_l = TT(G_l^1..G_l^L)                   dense layer matrix from its cores
  G   = U(Φ_u) Σ V(Φ_v)ᵀ                   TONN: each core unfolding an MZI mesh
  Φ_eff = Ω(Γ ⊙ Φ) + Φ_b                   fabrication noise (gamma, crosstalk, bias)
  f(x) = w2 · sin(W_1 sin(W_0 x̂ + b_0) + b_1) + b_2,  x̂ = x zero-padded
  u(x, t) = (1 − t) f + ‖x‖₁               HJB ansatz (terminal condition exact)
Loss: mean over the batch of the squared HJB residual
  u_t + Δu − λ‖∇u‖² + 2,  derivatives by central differences at step h,
with layer 0 evaluated once per point and shifted by ±h·W_0[:, i] (the
configuration's ``fd_fast`` stencil: layer 0 is linear, so this is exact).

Every matrix product goes through ``dot(a, b, precision)``: ``"highest"``
is full float32; ``"high"`` is the three-pass bfloat16 product
(hi·hi + hi·lo + lo·hi), emulated explicitly so that it means the same on
every backend.  The control of the benchmark's comparison runs this
reference at ``"high"``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

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

def core_shapes(cfg: dict) -> list:
    """(r_{k-1}, m_k, n_k, r_k) of each TT core."""
    r, m, n = cfg["tt_ranks"], cfg["tt_out_modes"], cfg["tt_in_modes"]
    return [(r[k], m[k], n[k], r[k + 1]) for k in range(len(m))]


def rectangular_levels(ports: int) -> list:
    """Rectangular (Clements) MZI mesh: column c pairs wires (a, a+1) for
    a = c mod 2, c mod 2 + 2, ...; empty columns are dropped."""
    cols = [[(a, a + 1) for a in range(c % 2, ports - 1, 2)]
            for c in range(ports)]
    return [c for c in cols if c]


def phase_shape(ports: int) -> tuple:
    levels = rectangular_levels(ports)
    return (len(levels), max(len(c) for c in levels))


# -------------------------------------------------------------------- init

def init_params(cfg: dict, key) -> dict:
    """Seeded initial parameters (paper §4: glorot-scaled TT cores)."""
    H = cfg["hidden"]
    keys = jax.random.split(key, 8)
    shapes = core_shapes(cfg)
    L = len(shapes)
    M, N = int(np.prod(cfg["tt_out_modes"])), int(np.prod(cfg["tt_in_modes"]))
    n_paths = float(np.prod(cfg["tt_ranks"][1:-1]))
    per_core_var = (2.0 / (N + M) / n_paths) ** (1.0 / L)
    params = {}
    for i in range(2):
        sub = jax.random.split(keys[i], L)
        params[f"pcores{i}"] = [
            _init_mesh_matrix(sub[k], r * m, n * rn, math.sqrt(per_core_var))
            for k, (r, m, n, rn) in enumerate(shapes)]
        params[f"b{i}"] = jnp.zeros((H,))
    params["w2"] = (math.sqrt(2.0 / (1 + H))
                    * jax.random.normal(keys[6], (1, H)))
    params["b2"] = jnp.zeros((1,))
    return params


def _init_mesh_matrix(key, out_dim: int, in_dim: int, std: float) -> dict:
    ku, kv, ks = jax.random.split(key, 3)
    k = min(out_dim, in_dim)
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
    shapes = core_shapes(cfg)
    out = {}
    for i in range(2):
        keys = jax.random.split(jax.random.fold_in(key, i), len(shapes))
        out[f"pcores{i}"] = []
        for k, (r, m, n, rn) in enumerate(shapes):
            ku, kv = jax.random.split(keys[k])
            out[f"pcores{i}"].append(
                {"u": _noise(nz, ku, phase_shape(r * m)),
                 "v": _noise(nz, kv, phase_shape(n * rn))})
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


def mesh_apply(phases, diag, x, transpose: bool):
    """Rotate the columns of x (B, P) through the mesh, one MZI column at a
    time: R(φ) = [[cos φ, −sin φ], [sin φ, cos φ]] on each wire pair.  The
    mesh is U = R_C ⋯ R_1 D; ``transpose`` applies Uᵀ = D R_1ᵀ ⋯ R_Cᵀ."""
    levels = rectangular_levels(x.shape[-1])
    order = range(len(levels))
    if transpose:
        order = reversed(order)
    else:
        x = x * diag
    for c in order:
        a = np.asarray([p[0] for p in levels[c]])
        b = np.asarray([p[1] for p in levels[c]])
        ph = phases[c, :len(levels[c])]
        cos, sin = jnp.cos(ph), jnp.sin(ph)
        if transpose:
            sin = -sin
        xa, xb = x[:, a], x[:, b]
        x = x.at[:, a].set(cos * xa - sin * xb).at[:, b].set(sin * xa + cos * xb)
    if transpose:
        x = x * diag
    return x


def mesh_matrix_dense(pm: dict, noise: dict | None, nz: dict, out_dim: int,
                      in_dim: int):
    """W = U Σ Vᵀ of one MZI-mesh matrix, columns W e_j."""
    pu, pv = pm["phases_u"], pm["phases_v"]
    if noise is not None:
        pu = effective_phases(nz, pu, noise["u"])
        pv = effective_phases(nz, pv, noise["v"])
    k = min(out_dim, in_dim)
    z = mesh_apply(pv, pm["diag_v"], jnp.eye(in_dim, dtype=jnp.float32),
                   transpose=True)
    z = z[:, :k] * pm["sigma"]
    z = jnp.pad(z, [(0, 0), (0, out_dim - k)])
    return mesh_apply(pu, pm["diag_u"], z, transpose=False).T


def tt_dense(cores: list, cfg: dict, precision: str):
    """W[(i_1..i_L), (j_1..j_L)] = Σ_ranks Π_k G_k[r, i_k, j_k, r']."""
    m, n = cfg["tt_out_modes"], cfg["tt_in_modes"]
    L = len(cores)
    t = cores[0].reshape(m[0] * n[0], -1)            # (i1 j1, r1)
    for k in range(1, L):
        g = cores[k]
        t = dot(t, g.reshape(g.shape[0], -1), precision)
        t = t.reshape(-1, g.shape[-1])
    t = t.reshape([d for k in range(L) for d in (m[k], n[k])])
    perm = list(range(0, 2 * L, 2)) + list(range(1, 2 * L, 2))
    return jnp.transpose(t, perm).reshape(int(np.prod(m)), int(np.prod(n)))


def dense_weights(params: dict, noise: dict | None, cfg: dict,
                  precision: str) -> tuple:
    """(W_0, W_1) of one parameter set."""
    out = []
    for i in range(2):
        cores = []
        for k, (r, m, n, rn) in enumerate(core_shapes(cfg)):
            nzk = None if noise is None else noise[f"pcores{i}"][k]
            w = mesh_matrix_dense(params[f"pcores{i}"][k], nzk, cfg["noise"],
                                  r * m, n * rn)
            cores.append(w.reshape(r, m, n, rn))
        out.append(tt_dense(cores, cfg, precision))
    return tuple(out)


# ----------------------------------------------------------------- forward

def ansatz(f, pts, space_dim: int):
    x, t = pts[..., :space_dim], pts[..., space_dim]
    return (1.0 - t) * f + jnp.sum(jnp.abs(x), axis=-1)


def u_points(params: dict, noise: dict | None, pts, cfg: dict,
             precision: str):
    """u at query points (n, in_dim), plain forward."""
    w0, w1 = dense_weights(params, noise, cfg, precision)
    z0 = dot(pts, w0[:, :pts.shape[-1]].T, precision) + params["b0"]
    z1 = dot(jnp.sin(z0), w1.T, precision) + params["b1"]
    f = jnp.sum(jnp.sin(z1) * params["w2"], axis=-1) + params["b2"][0]
    return ansatz(f, pts, cfg["space_dim"])


def residual_loss(params: dict, noise: dict | None, xt, cfg: dict,
                  precision: str, rows=None):
    """Mean squared HJB residual over the collocation batch ``xt``;
    ``rows`` limits the mean to a slice of the batch."""
    D = cfg["space_dim"]
    A = D + 1
    h = cfg["fd_step"]
    lam = 1.0 / D
    w0, w1 = dense_weights(params, noise, cfg, precision)
    z0 = dot(xt, w0[:, :A].T, precision) + params["b0"]          # (B, H)
    hcols = h * w0[:, :A].T                                       # (A, H)
    z = jnp.concatenate([z0[None], z0[None] + hcols[:, None],
                         z0[None] - hcols[:, None]], axis=0)      # (2A+1,B,H)
    z1 = dot(jnp.sin(z), w1.T, precision) + params["b1"]
    f = jnp.sum(jnp.sin(z1) * params["w2"], axis=-1) + params["b2"][0]
    e = jnp.eye(A, A, dtype=xt.dtype) * jnp.asarray(h, dtype=xt.dtype)
    pts = jnp.concatenate([xt[None], xt[None] + e[:, None, :],
                           xt[None] - e[:, None, :]], axis=0)
    u = ansatz(f, pts, D)
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
    for s in range(steps):
        xt = collocation(cfg, seed, s, job["batch"])
        params, zkey, base, grad, stack = step(params, noise, zkey, xt)
        out["loss"].append(float(base))
        if s == 0:
            out["p1"], out["grad0"] = params, grad
            out["stack0"] = [float(x) for x in stack]
    out["p_last"] = params
    return out
