"""Distribution tests on 8 forced host devices: sharding rules, dry-run
lowering on a small mesh, elastic remesh, pipeline parallelism, and
distributed ZO under shard_map.  (conftest keeps other test files at 1
device; this file re-execs itself under XLA_FLAGS in a subprocess when the
device count is wrong.)"""

import os
import subprocess
import sys

import pytest

NEEDS = 8

if os.environ.get("XLA_FLAGS", "").find("host_platform_device_count") < 0:
    # Re-run this test module in a subprocess with 8 host devices.
    @pytest.mark.slow
    def test_distribution_suite_subprocess():
        env = dict(os.environ)
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={NEEDS} "
                            + env.get("XLA_FLAGS", ""))
        env["REPRO_DIST_INNER"] = "1"
        r = subprocess.run(
            [sys.executable, "-m", "pytest", __file__, "-q", "-x"],
            env=env, capture_output=True, text=True, timeout=3000)
        sys.stdout.write(r.stdout[-4000:])
        sys.stderr.write(r.stderr[-2000:])
        assert r.returncode == 0
else:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.checkpoint import remesh_checkpoint, save_checkpoint, \
        restore_checkpoint
    from repro.core import zoo
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.parallel import sharding as shd
    from repro.parallel.pipeline import pipeline_forward, bubble_fraction

    def _mesh(d, m, names=("data", "model")):
        return make_mesh((d, m), names)

    def test_param_rules_cover_all_archs():
        mesh = _mesh(4, 2)
        for arch in configs.ARCH_NAMES:
            cfg = configs.get_config(arch)
            aparams = api.abstract_params(cfg)
            report = shd.ShardingReport(fallbacks=[])
            shardings = shd.param_shardings(mesh, aparams, report)
            norule = [f for f in report.fallbacks if "NO RULE" in f]
            assert not norule, (arch, norule)

    def test_small_mesh_train_lowering_runs():
        """An actually-executable sharded train step on 4x2 devices."""
        from repro.optim import get_optimizer
        from repro.parallel.act import activation_sharding
        mesh = _mesh(4, 2)
        cfg = configs.get_reduced("qwen2.5-3b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        report = shd.ShardingReport(fallbacks=[])
        ps = shd.param_shardings(
            mesh, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                               params), report)
        params = jax.tree.map(jax.device_put, params, ps)
        opt = get_optimizer("adamw")
        opt_state = opt.init(params)
        tokens = jnp.zeros((8, 64), jnp.int32)
        batch = {"tokens": tokens, "labels": tokens}

        with mesh, activation_sharding(mesh):
            @jax.jit
            def step(p, s, b):
                loss, g = jax.value_and_grad(
                    lambda q: api.loss_fn(q, cfg, b))(p)
                p2, s2 = opt.update(g, s, p)
                return p2, s2, loss
            p2, s2, loss = step(params, opt_state, batch)
        assert bool(jnp.isfinite(loss))

    def test_sharded_matches_single_device():
        """Same reduced model, same batch: loss on a 4x2 mesh must equal the
        unsharded loss (GSPMD is semantics-preserving)."""
        cfg = configs.get_reduced("yi-6b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                    cfg.vocab_size)
        batch = {"tokens": tokens, "labels": tokens}
        loss_ref = api.loss_fn(params, cfg, batch)

        mesh = _mesh(4, 2)
        report = shd.ShardingReport(fallbacks=[])
        ps = shd.param_shardings(
            mesh, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                               params), report)
        params_s = jax.tree.map(jax.device_put, params, ps)
        with mesh:
            loss_sharded = jax.jit(lambda p, b: api.loss_fn(p, cfg, b))(
                params_s, batch)
        np.testing.assert_allclose(float(loss_ref), float(loss_sharded),
                                   rtol=1e-4)

    def test_elastic_remesh_8_to_4():
        cfg = configs.get_reduced("qwen2.5-3b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        mesh8 = _mesh(4, 2)
        report = shd.ShardingReport(fallbacks=[])
        p8 = remesh_checkpoint(params, mesh8, report)
        # shrink to 4 devices (lost "half a pod")
        mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                     ("data", "model"))
        p4 = remesh_checkpoint(jax.tree.map(np.asarray, jax.device_get(p8)),
                               mesh4, report)
        tokens = jnp.zeros((4, 16), jnp.int32)
        batch = {"tokens": tokens, "labels": tokens}
        l8 = api.loss_fn(params, cfg, batch)
        with mesh4:
            l4 = jax.jit(lambda p, b: api.loss_fn(p, cfg, b))(p4, batch)
        np.testing.assert_allclose(float(l8), float(l4), rtol=1e-4)

    def test_pipeline_forward_matches_sequential():
        mesh = make_mesh((4, 2), ("pod", "model"))
        P_STAGES, LAYERS_PER = 4, 2
        d = 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (P_STAGES, LAYERS_PER, d, d)) * 0.3

        def stage_fn(w, h):
            for i in range(LAYERS_PER):
                h = jnp.tanh(h @ w[i])
            return h

        x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
        # sequential reference
        h = x
        for s in range(P_STAGES):
            h = stage_fn(ws[s], h)
        out = pipeline_forward(mesh, stage_fn, ws, x,
                               num_microbatches=4, axis="pod")
        np.testing.assert_allclose(np.asarray(out), np.asarray(h),
                                   atol=1e-5, rtol=1e-5)
        assert 0 < bubble_fraction(4, 4) < 1

    def test_distributed_zo_under_shard_map():
        """The scalar-only ZO protocol end-to-end under shard_map over 8
        devices: result must equal the single-host gradient."""
        mesh = make_mesh((8,), ("workers",))
        target = jnp.asarray(np.random.RandomState(0).randn(16).astype(np.float32))
        loss_fn = lambda p: jnp.sum((p["w"] - target) ** 2)
        params = {"w": jnp.zeros(16)}
        cfg = zoo.SPSAConfig(num_samples=8, mu=1e-2)
        key = jax.random.PRNGKey(3)
        base = loss_fn(params)
        g_ref, _ = zoo.spsa_gradient(loss_fn, params, key, cfg,
                                     base_loss=base)

        def worker(_):
            w = jax.lax.axis_index("workers")
            losses = zoo.spsa_losses(loss_fn, params, key, cfg,
                                     index_shard=None)
            # each worker contributes 1 sample: mask to its slice
            mask = (jnp.arange(cfg.num_samples) == w)
            merged = jax.lax.psum(losses * mask, "workers")
            g = zoo.spsa_gradient_from_losses(params, key, merged, base, cfg)
            return g["w"]

        g = jax.shard_map(worker, mesh=mesh, in_specs=(P("workers"),),
                          out_specs=P(None), check_vma=False)(
            jnp.zeros((8, 1)))
        np.testing.assert_allclose(np.asarray(g[0] if g.ndim > 1 else g),
                                   np.asarray(g_ref["w"]), rtol=1e-5)

    # ------------------------------------------------ distributed ZO (mesh)
    # repro.parallel.zo_shard: the SPSA sweep sharded end to end over an
    # explicit ("pert", "batch") mesh — gradient identity across every
    # layout, O(N)-scalar traffic, elastic 8 → 4 resume.

    from repro.core import pinn as pinn_lib
    from repro.parallel import zo_shard

    # 1×, 2×, and 8× devices; perturbation, batch, and both axes.  N=6 makes
    # n_total=7 indivisible by 2/4/8, exercising the zero-padded slices.
    ZO_LAYOUTS = [("1x1", "perturbation"), ("2x1", "perturbation"),
                  ("8x1", "perturbation"), ("1x2", "batch"), ("1x8", "batch"),
                  ("2x2", "both"), ("4x2", "both"), ("2x4", "both")]

    def _quad_batched_loss(target):
        def blf(sp, xt):
            d = sp["w"][:, None, :] - target[None, None, :] \
                + 0.0 * xt[None, :, :1]
            return jnp.mean(jnp.sum(d * d, axis=-1), axis=-1)
        return blf

    def test_zo_shard_gradient_identity_all_layouts():
        """Every mesh layout must reproduce the single-device fused SPSA
        gradient (pure perturbation sharding: bit-identical; batch sharding:
        f32 batch-mean reassociation only)."""
        target = jnp.asarray(
            np.random.RandomState(0).randn(16).astype(np.float32))
        params = {"w": jnp.zeros(16)}
        cfg = zoo.SPSAConfig(num_samples=6, mu=1e-2)
        key = jax.random.PRNGKey(3)
        xt = jax.random.normal(jax.random.PRNGKey(5), (16, 4))
        blf = _quad_batched_loss(target)
        lf = lambda p: jnp.sum((p["w"] - target) ** 2)
        g_ref, base_ref = jax.jit(
            lambda p, k: zoo.spsa_gradient(
                lf, p, k, cfg, batched_loss_fn=lambda sp: blf(sp, xt))
        )(params, key)
        for spec, shard in ZO_LAYOUTS:
            mesh = zo_shard.make_zo_mesh(spec, shard)
            grad_fn = zo_shard.make_distributed_spsa_gradient(mesh, blf, cfg)
            g, base = grad_fn(params, key, xt)
            np.testing.assert_allclose(
                np.asarray(g["w"]), np.asarray(g_ref["w"]),
                rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(g_ref["w"]))),
                err_msg=f"layout {spec} ({shard})")
            np.testing.assert_allclose(float(base), float(base_ref),
                                       rtol=1e-5, err_msg=spec)

    def _pinn_setup(pde="hjb-10d", hidden=32, batch=64, n=6, seed=0):
        # batch 64 keeps ≥8 collocation points per device on the 8-way
        # batch axis — the bit-stability threshold of the stacked
        # evaluator's GEMMs (DESIGN.md §Distributed)
        cfg = pinn_lib.PINNConfig(hidden=hidden, mode="tonn", tt_L=3,
                                  pde=pde, deriv="fd_fast",
                                  use_fused_kernel=True)
        model = pinn_lib.TensorPinn(cfg)
        key = jax.random.PRNGKey(seed)
        params = model.init(key)
        xt = model.problem.sample_collocation(jax.random.fold_in(key, 1),
                                              batch)
        scfg = zoo.SPSAConfig(num_samples=n, mu=1e-2)
        blf = lambda sp, x: pinn_lib.residual_losses_stacked(model, sp, x)
        return model, params, xt, scfg, blf, jax.random.fold_in(key, 2)

    def test_zo_shard_gradient_identity_pinn():
        """The real workload: the fused tensor-PINN stacked evaluator
        through the distributed protocol, every layout vs the single-device
        fused gradient.  Loss-level f32 reassociation passes through the
        SPSA reconstruction linearly, so gradients agree to ~1e-4 relative
        of the gradient scale (DESIGN.md §Distributed)."""
        model, params, xt, scfg, blf, key = _pinn_setup()
        g_ref, base_ref = jax.jit(
            lambda p, k: zoo.spsa_gradient(
                lambda q: pinn_lib.residual_loss(model, q, xt), p, k, scfg,
                batched_loss_fn=lambda sp: blf(sp, xt)))(params, key)
        ref_leaves = jax.tree.leaves(g_ref)
        scale = max(float(jnp.max(jnp.abs(l))) for l in ref_leaves)
        for spec, shard in [("8x1", "perturbation"), ("1x8", "batch"),
                            ("4x2", "both")]:
            mesh = zo_shard.make_zo_mesh(spec, shard)
            grad_fn = zo_shard.make_distributed_spsa_gradient(mesh, blf, scfg)
            g, base = grad_fn(params, key, xt)
            for a, b in zip(jax.tree.leaves(g), ref_leaves):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-4 * scale,
                    rtol=1e-3, err_msg=f"layout {spec} ({shard})")
            np.testing.assert_allclose(float(base), float(base_ref),
                                       rtol=1e-4, err_msg=spec)

    def test_zo_shard_traffic_is_scalar_only():
        """The compiled distributed step moves O(N) f32 scalars per step —
        never a parameter-sized tensor (the paper's scaling claim)."""
        model, params, xt, scfg, blf, key = _pinn_setup()
        mesh = zo_shard.make_zo_mesh("4x2", "both")
        step = zo_shard.make_distributed_zo_step(
            mesh, lambda sp, x, bc: blf(sp, x), scfg, donate=False)
        state = zoo.ZOState.create(0)
        traffic = zo_shard.measure_collective_bytes(
            step, params, state, xt, None, 1e-3)
        bound = zo_shard.wire_bound_bytes(scfg.num_samples, 4)
        n_param_bytes = 4 * sum(int(np.prod(x.shape))
                                for x in jax.tree.leaves(params))
        assert traffic["bytes"] > 0, "no collectives found in compiled HLO"
        assert traffic["bytes"] <= bound, traffic
        assert traffic["bytes"] < n_param_bytes, \
            f"parameter-sized transfer: {traffic}"

    def test_zo_shard_local_replica_is_one_device():
        """The step leaves params replicated over the mesh; validation runs
        on ``local_replica`` — the same values on one device, where a Pallas
        forward needs no GSPMD partitioning."""
        model, params, xt, scfg, blf, key = _pinn_setup()
        mesh = zo_shard.make_zo_mesh("8x1", "perturbation")
        step = zo_shard.make_distributed_zo_step(
            mesh, lambda sp, x, bc: blf(sp, x), scfg, donate=False)
        new, _, _ = step(params, zoo.ZOState.create(0), xt, None, 1e-3)
        local = zo_shard.local_replica(new)
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(local)):
            assert len(a.sharding.device_set) == 8
            assert b.sharding.device_set == {jax.devices()[0]}
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(model.u(local, xt)), np.asarray(model.u(new, xt)))

    def test_zo_shard_elastic_resize_8_to_4(tmp_path):
        """Checkpoint on an 8-device mesh, resume on 4: the loss trajectory
        must continue exactly as the uninterrupted 8-device run (replicated
        params + layout-invariant gradients ⇒ nothing depends on the mesh)."""
        from repro.checkpoint import CheckpointManager
        from repro.runtime import ZOElasticController
        model, params, xt, scfg, blf, _ = _pinn_setup()
        state = zoo.ZOState.create(7)
        make_mesh = lambda n: zo_shard.make_zo_mesh(
            str(n), "perturbation", devices=jax.devices()[:n])
        build = lambda mesh: zo_shard.make_distributed_zo_step(
            mesh, lambda sp, x, bc: blf(sp, x), scfg, donate=False)
        ckpt = CheckpointManager(tmp_path, keep=2, save_every=1)
        ctl = ZOElasticController(ckpt=ckpt, make_mesh=make_mesh,
                                  build_step=build)

        step8 = build(make_mesh(8))
        losses8 = []
        for _ in range(2):
            params, state, loss = step8(params, state, xt, None, 1e-3)
            losses8.append(float(loss))
        ckpt.save(2, {"params": params, "zo": state}, {"step": 2})
        p_ref, s_ref = params, state
        for _ in range(3):
            p_ref, s_ref, loss = step8(p_ref, s_ref, xt, None, 1e-3)
            losses8.append(float(loss))

        mesh4, step4, tree, meta = ctl.resume(
            4, {"params": jax.tree.map(jnp.zeros_like, params),
                "zo": zoo.ZOState.create(0)})
        assert meta["step"] == 2
        assert mesh4.shape["pert"] == 4
        p4, s4 = tree["params"], tree["zo"]
        losses4 = []
        for _ in range(3):
            p4, s4, loss = step4(p4, s4, xt, None, 1e-3)
            losses4.append(float(loss))
        # pure perturbation re-slicing: the resumed losses and params are
        # bit-identical to the uninterrupted run's
        np.testing.assert_allclose(losses4, losses8[2:], rtol=1e-6)
        for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-7)
