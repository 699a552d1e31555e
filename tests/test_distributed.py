"""Multi-device SPMD tests (subprocess: device count is locked at jax init).

Small placeholder-device meshes validate the same code paths the 512-device
dry-run uses: the flat multi-cluster LMC step under data/model sharding, and
an LM train step with the full production sharding rules.
"""

from _spmd import run_spmd as _run


def test_distributed_lmc_step_matches_single_device():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.graph import make_sbm_dataset, partition_graph, ClusterSampler
        from repro.core import make_train_step, init_history, from_graph, LMC
        from repro.core.distributed import stack_batches, spmd_shardings
        from repro.core.history import HistoricalState
        from repro.dist.sharding import activation_sharding
        from repro.launch.mesh import make_mesh
        from repro.models import make_gnn

        g = make_sbm_dataset("ppi-cpu", seed=3)
        data = from_graph(g)
        parts = partition_graph(g, 8, seed=0)
        gnn = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 2)
        params = gnn.init_params(jax.random.key(0))
        s = ClusterSampler(g, 8, 1, parts=parts, seed=1)
        sgs = [s.build_batch(np.array([d])) for d in range(4)]
        flat = stack_batches(sgs)
        step = make_train_step(gnn, LMC, g.num_nodes)
        store = init_history(2, g.num_nodes, 32)

        # single device reference
        l_ref, g_ref, _, _ = jax.jit(step)(params, store, flat, data.x, data.self_w)

        # 4 data shards x 2 model shards
        mesh = make_mesh((4, 2), ("data", "model"))
        bsh, ssh, xsh, swsh, psh = spmd_shardings(mesh)
        store_sh = HistoricalState(h=ssh["h"], v=ssh["v"])
        params_sh = jax.tree.map(lambda _: psh, params)
        with activation_sharding(mesh):
            jstep = jax.jit(step, in_shardings=(params_sh, store_sh, bsh, xsh, swsh))
            l_spmd, g_spmd, _, _ = jstep(params, store, flat, data.x, data.self_w)
        assert abs(float(l_ref) - float(l_spmd)) < 1e-4, (l_ref, l_spmd)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_spmd)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)
        print("SPMD-OK")
    """)
    assert "SPMD-OK" in out


def test_multipod_lmc_step_matches_single_device():
    """The stacked LMC batch end-to-end on the 3-axis ("pod","data","model")
    mesh: rows shard over the fused pod×data axis, stores/features over
    (pod×data, model), all via spmd_shardings — numerics must match a single
    device (DESIGN.md §4; ROADMAP multi-pod dry-run cell)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.graph import make_sbm_dataset, partition_graph, ClusterSampler
        from repro.core import make_train_step, init_history, from_graph, LMC
        from repro.core.distributed import stack_batches, spmd_shardings
        from repro.core.history import HistoricalState
        from repro.dist.sharding import activation_sharding
        from repro.launch.mesh import make_mesh
        from repro.models import make_gnn

        g = make_sbm_dataset("ppi-cpu", seed=3)
        data = from_graph(g)
        parts = partition_graph(g, 8, seed=0)
        gnn = make_gnn("gcn", g.feature_dim, 32, g.num_classes, 2)
        params = gnn.init_params(jax.random.key(0))
        s = ClusterSampler(g, 8, 1, parts=parts, seed=1)
        # pod x data = 4 row-parallel ways -> stack 4 per-device clusters
        sgs = [s.build_batch(np.array([d])) for d in range(4)]
        flat = stack_batches(sgs)
        step = make_train_step(gnn, LMC, g.num_nodes)
        store = init_history(2, g.num_nodes, 32)

        l_ref, g_ref, st_ref, _ = jax.jit(step)(params, store, flat,
                                                data.x, data.self_w)

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        bsh, ssh, xsh, swsh, psh = spmd_shardings(mesh)
        store_sh = HistoricalState(h=ssh["h"], v=ssh["v"])
        params_sh = jax.tree.map(lambda _: psh, params)
        with activation_sharding(mesh):
            jstep = jax.jit(step, in_shardings=(params_sh, store_sh, bsh,
                                                xsh, swsh))
            l_3ax, g_3ax, st_3ax, _ = jstep(params, store, flat,
                                            data.x, data.self_w)
        assert abs(float(l_ref) - float(l_3ax)) < 1e-4, (l_ref, l_3ax)
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_3ax)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        # store updates (the halo-exchange collectives) must agree too
        np.testing.assert_allclose(np.asarray(st_ref.h), np.asarray(st_3ax.h),
                                   rtol=2e-3, atol=2e-4)
        print("MULTIPOD-OK")
    """)
    assert "MULTIPOD-OK" in out


def test_lm_train_step_spmd_small_mesh():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import reduced_config, SHAPES
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_cell
        import dataclasses

        cfg = dataclasses.replace(reduced_config("llama3.2-1b"), microbatches=2)
        mesh = make_mesh((4, 2), ("data", "model"))
        shape = ShapeConfig("tiny_train", "train", 64, 8)
        lm, step, args, shs = build_cell(cfg, shape, mesh)
        params = lm.init_params(jax.random.key(0))
        from repro.optim import make_optimizer
        opt = make_optimizer(cfg.optimizer)
        from repro.models.spec import PSpec
        opt_state = opt.init(params, lm.params_spec())
        batch = {"tokens": jnp.arange(8*64, dtype=jnp.int32).reshape(8, 64) % cfg.vocab,
                 "loss_mask": jnp.ones((8, 64), jnp.float32)}
        with mesh:
            p2, s2, m = jax.jit(step, in_shardings=shs)(params, opt_state, batch)
        assert np.isfinite(float(m["loss"])), m
        # params actually changed
        delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)))
        assert delta > 0
        print("LM-SPMD-OK", float(m["loss"]))
    """)
    assert "LM-SPMD-OK" in out


def test_decode_step_spmd_cache_sharding():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import reduced_config
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_cell

        cfg = reduced_config("qwen2.5-32b")
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeConfig("tiny_decode", "decode", 64, 4)
        lm, step, args, shs = build_cell(cfg, shape, mesh)
        params = lm.init_params(jax.random.key(0))
        caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), lm.abstract_cache(4, 64))
        tok = jnp.ones((4, 1), jnp.int32)
        with mesh:
            logits, caches2 = jax.jit(step, in_shardings=shs)(params, caches, tok, jnp.int32(3))
        assert np.isfinite(np.float32(logits)).all()
        print("DECODE-SPMD-OK")
    """)
    assert "DECODE-SPMD-OK" in out
