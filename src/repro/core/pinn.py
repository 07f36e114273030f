"""Physics-informed neural networks, problem-parameterized (§2.2, §4).

``TensorPinn`` is the paper's 3-layer sine MLP (in → n → n → 1) bound to a
``repro.pde.PDEProblem`` — the workload supplies the collocation domain, the
hard-constraint ansatz ``u = T(f, xt)``, the pointwise residual from a
``DerivativeEstimate``, and (optionally) a boundary term L_b and an exact
solution; the model supplies the four parametrizations:

  * ``dense`` — ideal digital weights (the "off-chip" pre-training model),
  * ``onn``   — every weight an SVD MZI-mesh ``PhotonicMatrix`` (paper's ONN),
  * ``tt``    — first two layers TT-compressed (digital TT baseline),
  * ``tonn``  — TT-cores whose unfoldings are themselves MZI meshes — the
                paper's proposed hardware; ZO training tunes the phases.

The paper's own benchmark is ``pde="hjb-20d"`` (Eq. 7, §4: exact ansatz
u = (1−t)·f + ‖x‖₁, TT 1024: 2×256 core params + 1024 = 1,536); the
registry adds heat / Black–Scholes / Helmholtz workloads on the same stack.

All forwards are pure functions of a params pytree → usable under
``jax.jit``, ``jax.grad`` (off-chip baselines) and the ZO optimizer
(on-chip, forward-only).  The fused multi-perturbation ZO hot path
(DESIGN.md §Perf: densify-once, stacked TT contraction, shared FD stencil)
is problem-generic — problems only plug in ``ansatz`` (broadcast over the
stacked perturbation axis) and ``residual`` (consuming the generic stencil
estimate); see DESIGN.md §PDE for the exact contract.

Deprecated aliases (``HJBPinn``, ``hjb_residual_loss``,
``hjb_residual_losses_stacked``, ``hjb_exact_solution``) keep the pre-registry
HJB-specific API importable.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro import pde as pde_lib
from repro.core import fastmath, photonic, spectral as spectral_lib, stein, tt
from repro.kernels import quant as quant_lib

# every matmul of the forward runs in full f32: the FD residual amplifies
# rounding in u by 1/h² (DESIGN.md §Perf), and TPU's DEFAULT precision
# rounds f32 operands to bf16
_HIGHEST = jax.lax.Precision.HIGHEST

__all__ = ["PINNConfig", "TensorPinn", "sample_collocation",
           "residual_loss", "residual_losses_stacked", "per_term_losses",
           "validation_mse", "config_to_meta", "config_from_meta",
           # deprecated HJB-specific aliases
           "HJBPinn", "hjb_exact_solution", "hjb_residual_loss",
           "hjb_residual_losses_stacked"]


def _out_head(h: jax.Array, w2: jax.Array) -> jax.Array:
    """``h @ w2ᵀ`` for the (1 × hidden) output layer, as a row-wise
    reduction: a row's value then does not depend on how many rows ride
    along.  A GEMV's rounding does (XLA:CPU tiles it by the batch size),
    which broke served-vs-direct bit-identity, and the FD stencil amplifies
    such differences 1/h²-fold.  h: (..., hidden), w2: (1, hidden) or
    stacked (P, 1, hidden)."""
    return jnp.sum(h * w2, axis=-1, keepdims=True)


@dataclasses.dataclass(frozen=True)
class PINNConfig:
    space_dim: int = 20         # deprecated: the PDE problem owns its dims;
    #                             honored only by the HJBPinn compat wrapper
    hidden: int = 1024
    mode: str = "tonn"          # dense | onn | tt | tonn
    tt_rank: int = 2            # paper: ranks [1,2,1,2,1]
    tt_L: int = 4               # paper: 1024 = [4,8,4,8] · [8,4,8,4]
    fd_step: float | None = None  # None → the bound problem's recommended
    #                               step (< collocation margin, f32-noise/
    #                               truncation sweet spot); an explicit
    #                               value always wins, even one equal to a
    #                               problem default
    deriv: str = "fd"           # fd | fd_fast | stein | spectral | auto
    #                             ("auto" defers to the bound problem's
    #                             ``estimator`` attribute; every shipped
    #                             problem says "fd", so auto-resolution is
    #                             bit-identical to the historical default)
    stein_sigma: float = 5e-2
    stein_samples: int = 32
    spectral_points: int | None = None  # line-grid size M for the spectral
    #                             estimator; None → the bound problem's
    #                             ``spectral_points`` (extent and
    #                             periodization always come from the
    #                             problem — they are domain facts)
    use_fused_kernel: bool = False  # route TT matvecs through the Pallas
    #                                 kernel dispatcher (repro.kernels.ops):
    #                                 Pallas kernels on TPU, jnp ref on CPU
    pde: str = "hjb-20d"        # registry name resolved by TensorPinn when
    #                             no problem instance is passed explicitly
    noise: photonic.NoiseModel = dataclasses.field(
        default_factory=lambda: photonic.NoiseModel(enabled=False))
    quant: quant_lib.QuantConfig = dataclasses.field(
        default_factory=lambda: quant_lib.QuantConfig(enabled=False))
    # quantization-aware training/inference (DESIGN.md §Quantization):
    # block-scaled int8/fp8 TT cores (quant.dtype) and finite-bit DAC
    # phases (quant.phase_bits).  SPSA is gradient-free, so fake-quant in
    # the loss is the whole QAT story — zoo/zo_shard see nothing new.
    # Disabled (the default) is a bit-exact no-op on every path.

    @property
    def in_dim(self) -> int:
        """Deprecated: (x, t) input width of the HJB compat path — the model
        takes its true input width from the bound ``PDEProblem``."""
        return self.space_dim + 1


def config_to_meta(cfg: PINNConfig) -> dict:
    """JSON-safe dict of a ``PINNConfig`` (NoiseModel nested) — the
    checkpoint-metadata form consumed by ``repro.serving.SolverRegistry``,
    so a trained-solver checkpoint is loadable by name with no config
    side-channel (DESIGN.md §Serving)."""
    return dataclasses.asdict(cfg)


def config_from_meta(meta: dict) -> PINNConfig:
    """Inverse of ``config_to_meta``.  Unknown keys are ignored so configs
    written by a NEWER repro version still load (forward compatibility);
    missing keys take the dataclass defaults (older checkpoints)."""
    fields = {f.name for f in dataclasses.fields(PINNConfig)}
    kw = {k: v for k, v in meta.items() if k in fields}
    if isinstance(kw.get("noise"), dict):
        nz_fields = {f.name for f in dataclasses.fields(photonic.NoiseModel)}
        kw["noise"] = photonic.NoiseModel(
            **{k: v for k, v in kw["noise"].items() if k in nz_fields})
    if isinstance(kw.get("quant"), dict):
        q_fields = {f.name for f in dataclasses.fields(quant_lib.QuantConfig)}
        kw["quant"] = quant_lib.QuantConfig(
            **{k: v for k, v in kw["quant"].items() if k in q_fields})
    return PINNConfig(**kw)


def hjb_exact_solution(xt: jax.Array) -> jax.Array:
    """Deprecated alias: ``pde.HJBProblem.exact_solution`` (u = ‖x‖₁+1−t)."""
    return pde_lib.HJBProblem().exact_solution(xt)


def sample_collocation(key: jax.Array, n: int, space_dim: int = 20,
                       margin: float = 0.02) -> jax.Array:
    """HJB-domain collocation sampler, kept for the pre-registry API.

    Bit-identical to ``pde.HJBProblem(space_dim, margin).sample_collocation``
    (uniform (x, t) ∈ [margin, 1−margin]^{D+1}; the margin keeps FD stencils
    away from the |x| kink at 0 and the domain boundary).
    """
    return pde_lib.HJBProblem(space_dim, margin).sample_collocation(key, n)


class TensorPinn:
    """The paper's 3-layer sine MLP in a chosen parametrization, solving a
    registered ``PDEProblem`` (``cfg.pde`` or an explicit instance)."""

    def __init__(self, cfg: PINNConfig,
                 problem: pde_lib.PDEProblem | None = None):
        self.cfg = cfg
        self.problem = problem if problem is not None \
            else pde_lib.get_problem(cfg.pde)
        # the problem owns the input geometry (cfg.space_dim is legacy):
        # ``in_dim`` is the physical (x[, t]) width — the only coordinates
        # FD stencils ever shift — while ``net_in`` adds the problem's
        # coefficient slots (DESIGN.md §Parameterized families).  The two
        # coincide for unconditioned problems, keeping every legacy path
        # bit-identical.
        self.space_dim = self.problem.space_dim
        self.in_dim = self.problem.in_dim
        self.net_in = self.problem.net_dim
        # width the network actually consumes: problems with an input
        # feature map (``embed_features`` — e.g. ns-2d's periodic Fourier
        # features) widen/narrow the row inside ``_embed``; everyone else
        # keeps feat_in == net_in, so the padding arithmetic below is
        # bit-identical to the pre-feature-map stack
        self.feat_in = (self.problem.feature_dim
                        if self.problem.has_feature_map else self.net_in)
        # effective FD step: an explicit config value wins; the None
        # sentinel defers to the problem's recommended step (the one its
        # residual_tol noise floor is documented at — DESIGN.md §PDE).
        # (The old sentinel compared against the dataclass DEFAULT, so an
        # explicitly-passed fd_step equal to it was silently replaced.)
        self.fd_step = (cfg.fd_step if cfg.fd_step is not None
                        else self.problem.fd_step)
        # quantization hooks take None when disabled so every consumer
        # early-returns to the exact unquantized code path (the f32
        # off-path invariant, DESIGN.md §Quantization)
        self._quant = cfg.quant if cfg.quant.enabled else None
        # stacked hot path: vectorized polynomial sine (XLA:CPU's jnp.sin is
        # a scalar libm call); ~2 ulp, within the FD noise floor (DESIGN.md
        # §Perf).  The sequential photonic-realism path keeps libm sin, and
        # so does onn: its network part of u is O(1) (tonn's ≈ 5e-5 of u),
        # so ~2 ulp of sine reach u's last bit, which the stencil's 1/h²
        # amplifies (cost on a TPU v5e: ≈ 2.5 ms of a 38.9 ms hjb-20d step).
        self._sin = (fastmath.fast_sin
                     if cfg.use_fused_kernel and cfg.mode != "onn"
                     else jnp.sin)
        h = cfg.hidden
        if cfg.mode in ("tt", "tonn", "onn"):
            # pad the input up to a TT-factorizable width (the paper folds
            # 21 → 1024 so layer 1 is a 1024×1024 TT matrix, or in onn mode
            # a pair of 1024-port meshes); coefficient slots (and
            # feature-map outputs) count toward the unpadded width
            self.in_pad = h if h >= self.feat_in else -(-self.feat_in // 8) * 8
        else:
            self.in_pad = self.feat_in
        # layer dims after padding the input up to the TT-factorizable size
        self.dims = [(h, self.in_pad), (h, h), (1, h)]
        # the TT layers' specs; dense and onn layers are plain matrices
        self.specs = []
        if cfg.mode in ("tt", "tonn"):
            self.specs = [
                tt.hjb_layer_spec(h, self.in_pad, L=cfg.tt_L, max_rank=cfg.tt_rank),
                tt.hjb_layer_spec(h, h, L=cfg.tt_L, max_rank=cfg.tt_rank),
            ]
        if cfg.mode == "onn":
            self.photonic = [photonic.PhotonicMatrix(m, n) for (m, n) in self.dims[:2]]
        if cfg.mode == "tonn":
            # each TT-core's (r·m × n·r') unfolding is an MZI-mesh matrix
            self.photonic_cores = [
                [photonic.PhotonicMatrix(r * m, n * rn) for (r, m, n, rn)
                 in spec.core_shapes]
                for spec in self.specs
            ]

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        params: dict = {}
        if cfg.mode == "dense":
            for i, (m, n) in enumerate(self.dims):
                std = math.sqrt(2.0 / (m + n))
                params[f"w{i}"] = std * jax.random.normal(keys[2 * i], (m, n))
                params[f"b{i}"] = jnp.zeros((m,))
        elif cfg.mode == "onn":
            for i, pm in enumerate(self.photonic):
                params[f"p{i}"] = pm.init(keys[i])
                params[f"b{i}"] = jnp.zeros((self.dims[i][0],))
            params["w2"] = (math.sqrt(2.0 / (1 + cfg.hidden))
                            * jax.random.normal(keys[6], (1, cfg.hidden)))
            params["b2"] = jnp.zeros((1,))
        elif cfg.mode in ("tt", "tonn"):
            for i, spec in enumerate(self.specs):
                if cfg.mode == "tt":
                    params[f"cores{i}"] = tt.tt_init(keys[i], spec)
                else:
                    sub = jax.random.split(keys[i], spec.L)
                    # scale each core mesh so the dense product has glorot var
                    n_paths = float(np.prod(spec.ranks[1:-1])) if spec.L > 1 else 1.0
                    tgt = 2.0 / (spec.in_dim + spec.out_dim)
                    per_core = (tgt / n_paths) ** (1.0 / spec.L)
                    params[f"pcores{i}"] = [
                        pm.init(sub[k], scale=math.sqrt(per_core))
                        for k, pm in enumerate(self.photonic_cores[i])
                    ]
                params[f"b{i}"] = jnp.zeros((self.dims[i][0],))
            params["w2"] = (math.sqrt(2.0 / (1 + cfg.hidden))
                            * jax.random.normal(keys[6], (1, cfg.hidden)))
            params["b2"] = jnp.zeros((1,))
        else:
            raise ValueError(cfg.mode)
        return params

    def trainable_mask(self, params: dict) -> dict:
        """Boolean pytree partitioning ``params`` into trainable leaves
        (True) and fixed buffers (False): the photonic modes carry the ±1
        ``diag_u``/``diag_v`` buffers of every ``PhotonicMatrix`` inside
        their params dicts, and ZO training must neither perturb nor
        sign-update them (``zoo.zo_signsgd_step(trainable_mask=...)``) —
        they pin each mesh to its orthogonal decomposition."""
        buffers = photonic.PHOTONIC_BUFFER_KEYS

        def is_trainable(path, leaf):
            del leaf
            return not any(
                isinstance(k, jax.tree_util.DictKey) and k.key in buffers
                for k in path)

        return jax.tree_util.tree_map_with_path(is_trainable, params)

    def sample_noise(self, key: jax.Array) -> dict | None:
        """Fabrication noise is sampled ONCE per physical chip and then fixed
        (on-chip training adapts to it; off-chip mapping suffers from it)."""
        cfg = self.cfg
        if not cfg.noise.enabled:
            return None
        if cfg.mode == "onn":
            keys = jax.random.split(key, len(self.photonic))
            return {f"p{i}": pm.sample_noise(keys[i], cfg.noise)
                    for i, pm in enumerate(self.photonic)}
        if cfg.mode == "tonn":
            out = {}
            for i, pms in enumerate(self.photonic_cores):
                keys = jax.random.split(jax.random.fold_in(key, i), len(pms))
                out[f"pcores{i}"] = [pm.sample_noise(keys[k], cfg.noise)
                                     for k, pm in enumerate(pms)]
            return out
        return None

    # --------------------------------------------------------------- forward
    def _densify_cores(self, params: dict, noise: dict | None, i: int,
                       stacked: bool = False) -> list:
        """TONN layer i: densify each (small) core mesh into its TT-core.

        ``stacked=True`` densifies a leading SPSA-perturbation axis S per
        core in ONE batched mesh pass (``PhotonicMatrix.to_dense_stacked``)
        — same noise selection and core reshape, one shared loop body for
        the scalar and stacked paths."""
        cfg = self.cfg
        spec = self.specs[i]
        cores = []
        for k, pm in enumerate(self.photonic_cores[i]):
            nz = None if noise is None else noise[f"pcores{i}"][k]
            densify = pm.to_dense_stacked if stacked else pm.to_dense
            # DAC phase quantization acts on the commanded mesh phases,
            # before the noise model, inside the densification
            w = densify(params[f"pcores{i}"][k], cfg.noise if nz else None,
                        nz, quant=self._quant)
            shape = w.shape[:1] if stacked else ()
            cores.append(w.reshape(shape + spec.core_shapes[k]))
        return cores

    def _densify_onn(self, params: dict, noise: dict | None,
                     stacked: bool = False) -> dict:
        """ONN layers 0 and 1: each ``W = U Σ Vᵀ`` densified through its
        meshes on the identity feed, with the hardware noise applied to the
        phases first (``stacked=True`` densifies a leading SPSA axis in ONE
        batched mesh pass, ``PhotonicMatrix.apply_stacked``).  The feed's
        rows come out as W's columns, kept as ``wt{i} = Wᵀ`` (in × out).
        Layer 0's input is zero-padded from ``feat_in`` to its mesh's
        ``in_pad`` ports, so only its first ``feat_in`` columns are ever
        read, and only those are densified.  The layers then multiply by
        ``wt{i}``: densifying a 1024-port layer pushes at most 1,024
        columns through its meshes, where applying them to the stencil's
        rows would push 4,300 (DESIGN.md §Photonic).

        The phases and noise pass an ``optimization_barrier`` first: where
        they are constants of a ``jit`` (a serving program, a closure),
        XLA would otherwise fold their cos/sin at compile time with a sine
        of its own, ≈ 1 ulp from the compiled one, and the answers would
        follow whether the weights were closed over."""
        cfg = self.cfg
        eff = {k: v for k, v in params.items() if k not in ("p0", "p1")}
        phases, noise = jax.lax.optimization_barrier(
            ({k: params[k] for k in ("p0", "p1")}, noise))
        for i, pm in enumerate(self.photonic):
            nz = None if noise is None else noise[f"p{i}"]
            apply = pm.apply_stacked if stacked else pm.apply
            rows = self.feat_in if i == 0 else pm.in_dim
            eff[f"wt{i}"] = apply(phases[f"p{i}"],
                                  jnp.eye(rows, pm.in_dim, dtype=jnp.float32),
                                  cfg.noise if nz else None, nz,
                                  quant=self._quant)
        return eff

    def _dense_layer(self, params: dict, i: int) -> jax.Array:
        """Layer i of a dense or onn model as (..., in, out): ``w{i}``
        transposed, or the densified ``wt{i}`` (onn layer 0: its first
        ``feat_in`` rows, those the zero-padded input reads)."""
        if self.cfg.mode == "dense":
            return jnp.swapaxes(params[f"w{i}"], -1, -2)
        return params[f"wt{i}"]

    def prepare_params(self, params: dict, noise: dict | None) -> tuple:
        """Hoist densification: TONN pcores → dense TT-cores, ONN meshes →
        dense layer matrices, ONCE per loss evaluation (the seed
        re-densified per ``_layer_matvec`` call, i.e. per FD stencil × per
        SPSA perturbation — DESIGN.md §Perf).

        Returns ``(effective_params, effective_noise)``; a no-op for modes
        whose forward consumes ``params`` directly (dense / tt) and for
        already-prepared dicts.
        """
        mode = self.cfg.mode
        if mode == "onn" and "p0" in params:
            return self._densify_onn(params, noise), None
        if mode != "tonn" or "cores0" in params:
            return params, noise
        eff = {k: v for k, v in params.items() if not k.startswith("pcores")}
        for i in range(len(self.specs)):
            eff[f"cores{i}"] = self._densify_cores(params, noise, i)
        return eff, None  # hardware noise is baked into the dense cores

    def _fq_cores(self, cores: list, stacked: bool = False) -> list:
        """Fake-quant TT cores for the unfused jnp chain (QAT semantics;
        the fused ops paths quantize via their own ``quant=`` hook).  A
        stacked list gets per-P block scales — matching the quantized
        kernel's ``(P, n_blocks)`` scale layout.  Passthrough when weight
        quantization is off."""
        q = self._quant
        if q is None or not q.weights:
            return cores
        if stacked:
            return [jax.vmap(lambda c: quant_lib.fake_quant(c, q))(c)
                    for c in cores]
        return [quant_lib.fake_quant(c, q) for c in cores]

    def _layer_matvec(self, params: dict, noise: dict | None, i: int,
                      x: jax.Array) -> jax.Array:
        """Layer-i matvec of one (prepared) parameter set."""
        cfg = self.cfg
        if cfg.mode in ("dense", "onn"):
            w = self._dense_layer(params, i)
            return jnp.matmul(x[..., :w.shape[-2]], w, precision=_HIGHEST)
        spec = self.specs[i]
        cores = params.get(f"cores{i}")
        if cores is None:  # unprepared tonn params: densify on the fly
            cores = self._densify_cores(params, noise, i)
        if cfg.use_fused_kernel:
            from repro.kernels import ops
            return ops.tt_linear(x, cores, spec, quant=self._quant)
        return tt.tt_matvec(self._fq_cores(cores), x, spec)

    def _embed(self, xt: jax.Array) -> jax.Array:
        """Raw rows (..., net_in) → network inputs (..., in_pad).

        Problems with an input feature map (``embed_features`` — e.g.
        ns-2d's periodic Fourier features) replace the row entirely;
        otherwise coefficient slots are normalized to [0,1] via the
        problem's ``CoeffSpec`` (so the net sees O(1) inputs whatever the
        raw coefficient units) and the physical coordinates pass through
        untouched.  Either way the row is zero-padded to the
        TT-factorizable width.  Unconditioned feature-map-free problems
        reduce this to exactly the legacy pad (bit-identical off-path)."""
        if self.problem.has_feature_map:
            h = self.problem.embed_features(xt)
        else:
            h = xt
            spec = self.problem.coeff_spec
            if spec is not None:
                h = jnp.concatenate(
                    [h[..., :self.in_dim],
                     spec.normalize(h[..., self.in_dim:self.net_in])], axis=-1)
        if self.in_pad > self.feat_in:
            pad = jnp.zeros(h.shape[:-1] + (self.in_pad - self.feat_in,),
                            h.dtype)
            h = jnp.concatenate([h, pad], axis=-1)
        return h

    def f(self, params: dict, xt: jax.Array, noise: dict | None = None) -> jax.Array:
        """Base network f(xt): (B, net_in) → (B,)."""
        params, noise = self.prepare_params(params, noise)
        h = self._embed(xt)
        for i in range(2):
            h = self._layer_matvec(params, noise, i, h) + params[f"b{i}"]
            h = jnp.sin(h)
        out = _out_head(h, params["w2"]) + params["b2"]
        return out[..., 0]

    def u(self, params: dict, xt: jax.Array, noise: dict | None = None) -> jax.Array:
        """Problem ansatz u = T(f, xt) — e.g. HJB's (1−t)·f + ‖x‖₁, which
        makes the terminal condition exact."""
        return self.problem.ansatz(self.f(params, xt, noise), xt)

    # -------------------------------------------------- incremental FD (perf)
    def _layer1_columns(self, params: dict, noise: dict | None) -> jax.Array:
        """Columns 0..in_dim of the (effective) first-layer matrix — the FD
        stencil only ever shifts the input by ±h·e_i, and layer 1 is linear,
        so its perturbed pre-activations are rank-1 updates of the base one.
        Cost: one (in_dim × hidden) extraction instead of 2·D extra layer-1
        matvecs per collocation point (EXPERIMENTS.md §Perf cell 3)."""
        eye = jnp.eye(self.in_dim, self.in_pad, dtype=jnp.float32)
        return self._layer_matvec(params, noise, 0, eye)      # (in_dim, H)

    def fd_u_stencil(self, params: dict, xt: jax.Array, h: float,
                     noise: dict | None = None) -> jax.Array:
        """u at [x, x+h·e_1, ..., x−h·e_A]: (2·in_dim+1, B) values with
        layer 1 computed ONCE (incremental rank-1 FD forward); the problem
        ansatz is applied pointwise at the perturbed coordinates.  Only the
        A = in_dim physical coordinates are shifted — coefficient slots are
        inputs the PDE never differentiates, and since the embedding is
        affine per slot the rank-1 column updates are untouched by
        conditioning."""
        cfg = self.cfg
        params, noise = self.prepare_params(params, noise)
        B = xt.shape[0]
        A = self.in_dim
        xp = self._embed(xt)
        z0 = self._layer_matvec(params, noise, 0, xp) + params["b0"]  # (B,H)
        cols = self._layer1_columns(params, noise)                    # (A,H)
        hcols = h * cols
        z = jnp.concatenate([z0[None],
                             z0[None] + hcols[:, None],               # +h e_i
                             z0[None] - hcols[:, None]], axis=0)      # (2A+1,B,H)
        a = jnp.sin(z)
        a = jnp.sin(self._layer_matvec(params, noise, 1,
                                       a.reshape(-1, cfg.hidden))
                    + params["b1"])
        f = (_out_head(a, params["w2"]) + params["b2"])[..., 0]
        f = f.reshape(2 * A + 1, B)
        return self.problem.ansatz(f, pde_lib.fd_stencil_points(xt, h, A))

    # --------------------------------------- stacked (multi-perturbation) ZO
    def prepare_params_stacked(self, stacked: dict, noise: dict | None) -> dict:
        """``prepare_params`` over a leading perturbation axis P on every
        leaf: every TONN core mesh and ONN layer mesh densifies all N+1
        SPSA-perturbed phase sets in ONE batched pass
        (``PhotonicMatrix.to_dense_stacked`` — the batched mesh engine,
        sharing the identity feed and the layout across the stack;
        hardware noise is shared too — one physical chip, and is baked
        into the result).  The seed vmapped the scalar ``prepare_params``
        instead, re-tracing the scatter-per-level mesh scan per
        perturbation."""
        mode = self.cfg.mode
        if mode == "onn" and "p0" in stacked:
            return self._densify_onn(stacked, noise, stacked=True)
        if mode != "tonn" or "cores0" in stacked:
            return stacked
        eff = {k: v for k, v in stacked.items() if not k.startswith("pcores")}
        for i in range(len(self.specs)):
            eff[f"cores{i}"] = self._densify_cores(stacked, noise, i,
                                                   stacked=True)
        return eff

    def _layer_matvec_stacked(self, stacked: dict, i: int,
                              x: jax.Array) -> jax.Array:
        """Layer-i matvec for P stacked (prepared) parameter sets.  x:
        (B', n) shared across the stack or (P, B', n) per-entry; returns
        (P, B', m)."""
        cfg = self.cfg
        if cfg.mode in ("dense", "onn"):
            w = self._dense_layer(stacked, i)                 # (P, in, out)
            x = x[..., :w.shape[-2]]
            if x.ndim == 2:
                x = jnp.broadcast_to(x, (w.shape[0],) + x.shape)
            # one entry at a time, each the sequential layer's product: XLA
            # on TPU may tile a stacked product by P, so entry p's rounding
            # would follow the stack size (DESIGN.md §Distributed)
            return jax.lax.map(lambda e: jnp.matmul(
                e[0], e[1], precision=_HIGHEST), (x, w))
        spec = self.specs[i]
        cores = stacked[f"cores{i}"]
        if cfg.use_fused_kernel:
            from repro.kernels import ops
            return ops.tt_linear_batched(x, cores, spec, quant=self._quant)
        return tt.tt_matvec_stacked(self._fq_cores(cores, stacked=True),
                                    x, spec)

    def _f_head_stacked(self, stacked: dict, a: jax.Array) -> jax.Array:
        """``f = sin(W1·a + b1) @ w2ᵀ + b2`` for P stacked parameter sets:
        (P, B', hidden) activations → (P, B') f-values.  The hidden layer
        goes through ``_layer_matvec_stacked``: on TPU the stacked
        contraction kernel takes the paper spec's Kronecker split itself
        (``ops.tt_path`` "kron")."""
        return self._head_stacked(stacked,
                                  self._layer_matvec_stacked(stacked, 1, a))

    def _head_stacked(self, stacked: dict, z: jax.Array) -> jax.Array:
        """``f = sin(z + b1) @ w2ᵀ + b2`` on the hidden layer's (P, B',
        hidden) pre-activations → (P, B') f-values."""
        from repro.kernels import ops
        b1, w2 = stacked["b1"], stacked["w2"]
        if ops.kernel_mode() == "pallas":
            # XLA on TPU tiles a stacked reduction's output by P, so entry
            # p's rounding would follow the stack size (DESIGN.md
            # §Distributed); a loop body's shapes do not depend on P
            f = jax.lax.map(lambda e: _out_head(self._sin(e[0] + e[1]), e[2]),
                            (z, b1, w2))
        else:
            f = _out_head(self._sin(z + b1[:, None]), w2)
        return (f + stacked["b2"][:, None])[..., 0]

    def fd_u_stencil_stacked(self, stacked: dict, xt: jax.Array,
                             h: float) -> jax.Array:
        """``fd_u_stencil`` for P stacked (prepared) parameter sets in one
        batched program: (P, 2·Din+1, B) u-values.  The collocation stencil
        is shared across the stack, so layer 1 reads x once per batch tile
        regardless of P (the fused-kernel analogue of TONN's one optical
        pass over all perturbed meshes); the problem ansatz broadcasts over
        the leading P axis."""
        cfg = self.cfg
        B = xt.shape[0]
        A = self.in_dim
        P = stacked["b0"].shape[0]
        xp = self._embed(xt)
        z0 = self._layer_matvec_stacked(stacked, 0, xp) \
            + stacked["b0"][:, None]                                  # (P,B,H)
        eye = jnp.eye(self.in_dim, self.in_pad, dtype=jnp.float32)
        cols = self._layer_matvec_stacked(stacked, 0, eye)            # (P,A,H)
        hcols = h * cols
        if self.stencil_prologue():
            # the kernel builds sin(z0 ± hcols) in VMEM, B rows padded to
            # Bp a slot; the pad rows' f-values are dropped
            from repro.kernels import ops
            z = ops.tt_linear_stencil(z0, hcols, stacked["cores1"],
                                      self.specs[1])
            f = self._head_stacked(stacked, z).reshape(P, 2 * A + 1, -1)
            f = f[:, :, :B]
        else:
            z = jnp.concatenate(
                [z0[:, None],
                 z0[:, None] + hcols[:, :, None],                     # +h e_i
                 z0[:, None] - hcols[:, :, None]], axis=1)     # (P,2A+1,B,H)
            a = self._sin(z).reshape(P, (2 * A + 1) * B, cfg.hidden)
            f = self._f_head_stacked(stacked, a).reshape(P, 2 * A + 1, B)
        return self.problem.ansatz(f, pde_lib.fd_stencil_points(xt, h, A))

    def stencil_prologue(self) -> bool:
        """True iff ``fd_u_stencil_stacked`` hands layer 1 the stencil's
        rank-1 factors (z0, h·W0 e_i) and the ``tt_contract`` kernel builds
        the rows itself: tt and tonn layers on the unquantized kernel
        (``ops.tt_stencil_prologue``).  Static per model and kernel mode."""
        from repro.kernels import ops
        return (self.cfg.mode in ("tt", "tonn") and self.cfg.use_fused_kernel
                and ops.tt_stencil_prologue(self.specs[1], quant=self._quant))

    def f_stacked(self, stacked: dict, xt: jax.Array) -> jax.Array:
        """Base network for P stacked (prepared) parameter sets over a
        SHARED input batch: (B, net_in) → (P, B)."""
        h = self._embed(xt)
        a = self._sin(self._layer_matvec_stacked(stacked, 0, h)
                      + stacked["b0"][:, None])
        return self._f_head_stacked(stacked, a)

    def u_stacked(self, stacked: dict, xt: jax.Array) -> jax.Array:
        """Ansatz u for P stacked parameter sets: (B, net_in) → (P, B)."""
        return self.problem.ansatz(self.f_stacked(stacked, xt), xt)

    # ------------------------------------------- coefficient-family queries
    def _coeff_rows(self, pts: jax.Array, coeffs: jax.Array) -> jax.Array:
        """(B, in_dim) physical points × (C, K) raw coefficient vectors →
        (C·B, net_in) augmented rows (C-major)."""
        if self.problem.coeff_spec is None:
            raise ValueError(
                f"PDE {self.problem.name!r} is not coefficient-conditioned")
        coeffs = jnp.asarray(coeffs, dtype=pts.dtype)
        C, K = coeffs.shape
        B = pts.shape[0]
        rows = jnp.concatenate(
            [jnp.broadcast_to(pts[None], (C, B, self.in_dim)),
             jnp.broadcast_to(coeffs[:, None, :], (C, B, K))], axis=-1)
        return rows.reshape(C * B, self.net_in)

    def u_coeff_grid(self, params: dict, pts: jax.Array, coeffs: jax.Array,
                     noise: dict | None = None) -> jax.Array:
        """u over the coefficient × point grid: (C, B) — the same physical
        batch evaluated under C scenarios through one flattened forward
        (every mode/kernel path works unchanged; the second batch axis is
        just more rows)."""
        C, B = coeffs.shape[0], pts.shape[0]
        return self.u(params, self._coeff_rows(pts, coeffs),
                      noise).reshape(C, B)

    def u_coeff_grid_stacked(self, stacked: dict, pts: jax.Array,
                             coeffs: jax.Array) -> jax.Array:
        """``u_coeff_grid`` for P stacked parameter sets: (P, C, B) — the
        perturbations × coefficients double batch of the conditioned ZO
        path, flattened through the stacked evaluator."""
        C, B = coeffs.shape[0], pts.shape[0]
        vals = self.u_stacked(stacked, self._coeff_rows(pts, coeffs))
        return vals.reshape(vals.shape[0], C, B)


class HJBPinn(TensorPinn):
    """Deprecated alias: ``TensorPinn`` bound to the paper's HJB problem
    (``cfg.space_dim`` spatial dims) — the pre-registry constructor."""

    def __init__(self, cfg: PINNConfig):
        super().__init__(cfg, problem=pde_lib.HJBProblem(cfg.space_dim))


# ---------------------------------------------------------------------- loss

def _loss_from_u_stencil(problem: pde_lib.PDEProblem, vals: jax.Array,
                         h: float, xt: jax.Array) -> jax.Array:
    """Residual loss from u-values at the central-difference stencil
    [x, x+h·e_1, ..., x−h·e_Din]: vals (2·Din+1, B) → scalar.  The generic
    stencil→DerivativeEstimate assembly is problem-independent; the problem
    supplies the estimate→residual reduction."""
    est = problem.scale_estimate(pde_lib.estimate_from_u_stencil(vals, h))
    r = problem.residual(est, xt)
    return jnp.mean(r * r)


def _boundary_mse(u_b: jax.Array, ub_target: jax.Array) -> jax.Array:
    """Mean-squared target mismatch (boundary- and data-term reduction),
    reduced over the trailing (batch) axis so it broadcasts over a leading
    stacked-perturbation axis."""
    return jnp.mean((u_b - ub_target) ** 2, axis=-1)


def _term_plan(problem: pde_lib.PDEProblem, bc: tuple | None,
               term_batches: dict | None) -> tuple:
    """Normalize the two batch-passing conventions into the term engine's
    execution plan: ``(collocation_weight, [(LossTerm, (x, target)), ...])``.

    ``term_batches`` is the native form — a dict keyed by term NAME (from
    ``problem.loss_terms()``) holding ``(x, target)`` batches for the
    non-collocation terms; the collocation batch is the positional ``xt``.
    Missing terms are simply not assembled this step (alternating-batch
    schedules); an entry of ``None`` is skipped the same way; unknown
    names raise.  ``bc=(xb, ub)`` is the deprecated pre-term-engine
    convention and maps onto the problem's (first) boundary-kind term —
    synthesized at ``bc_weight`` when the problem declares none, exactly
    the legacy ``L_r + λ·L_b`` arithmetic.  Passing both is ambiguous and
    raises."""
    if bc is not None and term_batches is not None:
        raise ValueError(
            "pass either bc= (deprecated) or term_batches=, not both")
    terms = problem.loss_terms()
    coll_w = next(
        (t.weight for t in terms if t.kind == "collocation"), 1.0)
    if bc is not None:
        b_terms = [t for t in terms if t.kind == "boundary"]
        term = b_terms[0] if b_terms else pde_lib.LossTerm(
            "boundary", "boundary", problem.bc_weight)
        return coll_w, [(term, bc)]
    if not term_batches:
        return coll_w, []
    known = {t.name: t for t in terms if t.kind != "collocation"}
    unknown = sorted(set(term_batches) - set(known))
    if unknown:
        raise ValueError(
            f"unknown loss term(s) {unknown} for PDE {problem.name!r}; "
            f"known non-collocation terms: {sorted(known)}")
    return coll_w, [(known[name], batch)
                    for name, batch in term_batches.items()
                    if batch is not None]


def _resolve_deriv(cfg: PINNConfig, problem: pde_lib.PDEProblem) -> str:
    """The estimator dispatch seam (DESIGN.md §Residual-estimators):
    ``cfg.deriv == "auto"`` defers to the problem's ``estimator``
    attribute; an explicit config value always wins.  One forced
    downgrade: ``fd_fast``'s incremental rank-1 stencil assumes the
    input embedding is affine per coordinate, which a problem feature
    map (``embed_features`` — e.g. ns-2d's Fourier features) breaks,
    so feature-map problems take the plain-fd stencil instead (same
    estimate, more layer-1 matvecs; no legacy behavior to preserve —
    no pre-feature-map problem has a feature map)."""
    deriv = problem.estimator if cfg.deriv == "auto" else cfg.deriv
    if deriv == "fd_fast" and problem.has_feature_map:
        return "fd"
    return deriv


def _spectral_grid(model: "TensorPinn") -> tuple:
    """(M, extent, periodization) for the bound problem — M from the
    config when set, the domain facts always from the problem."""
    problem = model.problem
    M = model.cfg.spectral_points or problem.spectral_points
    return M, problem.spectral_extent, problem.spectral_periodization


def _spectral_loss_terms(model: "TensorPinn", vals: jax.Array,
                         rows: jax.Array, xt: jax.Array) -> jax.Array:
    """Residual loss(es) from u-values over the spectral line rows:
    vals (..., R) → mean-squared residual with any leading axes (the
    stacked path feeds the (P, R) perturbation stack) reduced only over
    the anchor batch."""
    problem = model.problem
    M, extent, periodization = _spectral_grid(model)
    est = spectral_lib.estimate_from_line_vals(
        vals, xt, model.in_dim, M, extent, periodization,
        carrier=problem.spectral_carrier(rows, xt))
    est = problem.scale_estimate(est)
    r = problem.residual(est, xt)
    return jnp.mean(r * r, axis=-1)


def residual_loss(model: TensorPinn, params: dict, xt: jax.Array,
                  noise: dict | None = None,
                  key: jax.Array | None = None,
                  bc: tuple | None = None,
                  term_batches: dict | None = None) -> jax.Array:
    """BP-free composite PDE loss: the weighted sum of the problem's
    ``loss_terms()`` — the collocation residual L_r over ``xt``, plus
    ``weight · MSE(u(x), target)`` for every boundary/data term whose
    batch is supplied via ``term_batches={name: (x, target)}`` (paper
    Eq. 4 generalized; ``bc=(xb, ub)`` is the deprecated two-term form
    and stays bit-identical — see ``_term_plan``).

    Derivatives are estimated inference-only (FD, Stein or spectral per
    ``cfg.deriv``, "auto" deferring to ``problem.estimator``); the bound
    ``PDEProblem`` reduces the estimate to a pointwise residual, with
    ``scale_estimate`` folding the domain-normalization Jacobian in
    first (identity for unit-box problems).  TONN densification is
    hoisted here: ONE mesh→core pass per loss evaluation, shared by
    every stencil inference (DESIGN.md §Perf).
    """
    cfg = model.cfg
    problem = model.problem
    deriv = _resolve_deriv(cfg, problem)
    params, noise = model.prepare_params(params, noise)
    if deriv == "fd_fast":
        # incremental rank-1 FD forward: layer 1 computed once (§Perf cell 3)
        vals = model.fd_u_stencil(params, xt, model.fd_step, noise)
        loss = _loss_from_u_stencil(problem, vals, model.fd_step, xt)
    elif deriv == "spectral":
        M, extent, _ = _spectral_grid(model)
        rows = spectral_lib.spectral_line_rows(xt, model.in_dim, M, extent)
        loss = _spectral_loss_terms(
            model, model.u(params, rows, noise), rows, xt)
    else:
        f = lambda pts: model.u(params, pts, noise)
        if deriv == "fd":
            est = stein.fd_estimate(f, xt, h=model.fd_step,
                                    n_active=model.in_dim)
        else:
            assert key is not None, "stein estimator needs a PRNG key"
            est = stein.stein_estimate(f, xt, key, sigma=cfg.stein_sigma,
                                       num_samples=cfg.stein_samples,
                                       n_active=model.in_dim)
        est = problem.scale_estimate(est)
        r = problem.residual(est, xt)
        loss = jnp.mean(r * r)
    coll_w, plan = _term_plan(problem, bc, term_batches)
    if coll_w != 1.0:  # static: default weight keeps the legacy graph
        loss = coll_w * loss
    for t, (xb, ub) in plan:
        loss = loss + t.weight * _boundary_mse(
            model.u(params, xb, noise), ub)
    return loss


def residual_losses_stacked(model: TensorPinn, stacked_params: dict,
                            xt: jax.Array, noise: dict | None = None,
                            key: jax.Array | None = None,
                            bc: tuple | None = None,
                            term_batches: dict | None = None) -> jax.Array:
    """The ZO hot path: composite losses of P stacked parameter sets
    (leading axis on every leaf) over ONE shared collocation batch →
    (P,) losses.  Boundary/data terms ride the same stacked forward
    (``term_batches`` — the same term-engine contract as
    ``residual_loss``; ``bc`` is the deprecated two-term form).

    For dense/tt/tonn/onn with FD or spectral derivatives this runs as a
    small number of batched programs (densify-once via the batched mesh
    engine, stacked TT contraction via ``tt_linear_batched``, dense layer
    products in dense and onn mode, one shared stencil — or one shared set of spectral line rows, FFT'd per
    perturbation after the single stacked forward).  Other mode/estimator
    combinations (Stein derivatives) fall back to a vmap of the scalar
    loss — correct everywhere, fused where it matters.  The fallback
    SPLITS ``key`` per perturbation, so stochastic estimators (Stein)
    draw independent noise for each stacked entry: stacked entry i equals
    ``residual_loss(model, params_i, xt, noise, jax.random.split(key, P)[i])``.
    """
    cfg = model.cfg
    problem = model.problem
    deriv = _resolve_deriv(cfg, problem)
    if cfg.mode not in ("dense", "tt", "tonn", "onn") or \
            deriv not in ("fd", "fd_fast", "spectral"):
        if key is None:
            return jax.vmap(
                lambda p: residual_loss(model, p, xt, noise, None, bc,
                                        term_batches)
            )(stacked_params)
        P = jax.tree.leaves(stacked_params)[0].shape[0]
        keys = jax.random.split(key, P)
        return jax.vmap(
            lambda p, k: residual_loss(model, p, xt, noise, k, bc,
                                       term_batches)
        )(stacked_params, keys)
    # the photonic modes bake the (shared-chip) hardware noise into the
    # densified cores / layer matrices
    prepared = model.prepare_params_stacked(stacked_params, noise)
    if deriv == "spectral":
        M, extent, _ = _spectral_grid(model)
        rows = spectral_lib.spectral_line_rows(xt, model.in_dim, M, extent)
        vals = model.u_stacked(prepared, rows)     # (P, R)
        losses = _spectral_loss_terms(model, vals, rows, xt)  # (P,)
    else:
        h = model.fd_step
        if deriv == "fd_fast":
            vals = model.fd_u_stencil_stacked(prepared, xt, h)
        else:
            B, D = xt.shape
            A = model.in_dim  # coefficient slots are never differentiated
            pts = pde_lib.fd_stencil_points(xt, h, A)
            vals = model.u_stacked(prepared, pts.reshape(-1, D))
            vals = vals.reshape(vals.shape[0], 2 * A + 1, B)
        losses = jax.vmap(
            lambda v: _loss_from_u_stencil(problem, v, h, xt))(vals)
    coll_w, plan = _term_plan(problem, bc, term_batches)
    if coll_w != 1.0:  # static: default weight keeps the legacy graph
        losses = coll_w * losses
    for t, (xb, ub) in plan:
        losses = losses + t.weight * _boundary_mse(
            model.u_stacked(prepared, xb), ub)
    return losses


def per_term_losses(model: TensorPinn, params: dict, xt: jax.Array,
                    noise: dict | None = None,
                    key: jax.Array | None = None,
                    term_batches: dict | None = None) -> dict:
    """UNWEIGHTED per-term losses, keyed by term name — the logging /
    benchmark view of the composite loss (``residual_loss`` equals
    ``sum(w_t · per_term_losses[t])`` with the weights from
    ``problem.term_weights()``).  Terms whose batch is absent from
    ``term_batches`` are omitted from the dict."""
    problem = model.problem
    out = {}
    for t in problem.loss_terms():
        if t.kind == "collocation":
            out[t.name] = residual_loss(model, params, xt, noise, key)
        else:
            batch = (term_batches or {}).get(t.name)
            if batch is not None:
                xb, ub = batch
                out[t.name] = _boundary_mse(model.u(params, xb, noise), ub)
    return out


def validation_mse(model: TensorPinn, params: dict, xt: jax.Array,
                   noise: dict | None = None) -> jax.Array:
    """MSE against the problem's closed-form solution (raises without one)."""
    exact = model.problem.exact_solution(xt)
    if exact is None:
        raise ValueError(
            f"PDE {model.problem.name!r} has no exact solution; "
            "track the residual loss instead")
    pred = model.u(params, xt, noise)
    return jnp.mean((pred - exact) ** 2)


# ------------------------------------------------- deprecated HJB-era names

def hjb_residual_loss(model: TensorPinn, params: dict, xt: jax.Array,
                      noise: dict | None = None,
                      key: jax.Array | None = None) -> jax.Array:
    """Deprecated alias of ``residual_loss`` (works for any bound problem)."""
    return residual_loss(model, params, xt, noise, key)


def hjb_residual_losses_stacked(model: TensorPinn, stacked_params: dict,
                                xt: jax.Array, noise: dict | None = None,
                                key: jax.Array | None = None) -> jax.Array:
    """Deprecated alias of ``residual_losses_stacked``."""
    return residual_losses_stacked(model, stacked_params, xt, noise, key)
