"""Distributed-path tests: run in a subprocess with 8 forced host devices
(the main pytest process must keep 1 device for the rest of the suite;
see tests/_multidevice.py, the shared subprocess helper).

Covers: shard_map expert-parallel MoE == local math, a sharded train step
on the (data, model) mesh with the production param specs, and the
mesh-aware ``constrain`` helper.
"""

from _multidevice import run_multidevice as _run


def test_shard_map_moe_matches_local():
    print(_run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.models import build_model
        from repro.models.layers import moe_block

        cfg = get_arch("kimi-k2-1t-a32b").reduced()
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        x = jax.random.normal(jax.random.key(2), (4, 16, cfg.d_model)) * 0.5
        moe_p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
        out_ref, _ = moe_block(moe_p, x, cfg)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            out_sm, _ = jax.jit(lambda p_, x_: moe_block(p_, x_, cfg))(moe_p, x)
        err = float(jnp.abs(out_ref - out_sm).max())
        assert err < 1e-5, err
        print("moe shard_map equivalence ok", err)
    """))


def test_sharded_train_step_runs_and_matches_single_device():
    print(_run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.models import build_model
        from repro.launch.steps import init_train_state, make_train_step
        from repro.launch import sharding as shd
        from repro.optim import adamw

        cfg = get_arch("qwen3-8b").reduced()
        model = build_model(cfg)
        opt = adamw(1e-3)
        state = init_train_state(model, opt, jax.random.key(0))
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (4, 64), 0, cfg.vocab_size)
        }
        # single-device reference
        ref_state, ref_metrics = jax.jit(make_train_step(model, opt))(state, batch)
        ref_loss = float(ref_metrics["loss"])

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        state_shapes = jax.eval_shape(lambda: state)
        state_specs = {
            "params": shd.tree_param_specs(
                state_shapes["params"], mesh, n_kv_heads=cfg.n_kv_heads
            ),
            "opt": {
                k: shd.tree_param_specs(v, mesh, n_kv_heads=cfg.n_kv_heads)
                for k, v in state_shapes["opt"].items()
            },
            "step": jax.sharding.PartitionSpec(),
        }
        batch_specs = shd.batch_spec(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}, mesh
        )
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                make_train_step(model, opt),
                in_shardings=(
                    shd.to_named(state_specs, mesh),
                    shd.to_named(batch_specs, mesh),
                ),
            )
            state2 = jax.device_put(state, shd.to_named(state_specs, mesh))
            batch2 = jax.device_put(batch, shd.to_named(batch_specs, mesh))
            new_state, metrics = jitted(state2, batch2)
            loss = float(metrics["loss"])
        assert abs(loss - ref_loss) < 1e-2, (loss, ref_loss)
        # params agree between single-device and sharded step
        diff = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(a - jax.device_get(b)))),
            ref_state["params"], jax.device_get(new_state["params"]))
        assert max(jax.tree.leaves(diff)) < 5e-2
        print("sharded train step ok", loss, ref_loss)
    """))


def test_constrain_filters_indivisible_dims():
    print(_run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.util import constrain

        mesh = make_mesh((2, 4), ("data", "model"))

        @jax.jit
        def f(x):
            # 7 doesn't divide 4 -> model entry must be dropped, not crash
            return constrain(x, P("data", "model")) * 2

        with jax.set_mesh(mesh):
            out = f(jnp.ones((8, 7)))
        assert out.shape == (8, 7)
        print("constrain divisibility guard ok")
    """))


def test_set_mesh_scope_drives_constrain():
    """``constrain`` binds only inside ``jax.set_mesh``: outside it is
    the identity, inside it places by the spec, and leaving the block
    restores the empty ambient mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    from repro.util import constrain

    x = jnp.ones((4, 4))
    assert constrain(x, P("data")) is x
    mesh = make_host_mesh()
    f = jax.jit(lambda a: constrain(a, P("data", "model")) * 2)
    with jax.set_mesh(mesh):
        assert not jax.sharding.get_abstract_mesh().empty
        assert "sharding_constraint" in f.lower(x).as_text()
        out = f(x)
    assert jax.sharding.get_abstract_mesh().empty
    assert "sharding_constraint" not in f.lower(x).as_text()
    assert float(out.sum()) == 32.0


def test_multipod_mesh_axes():
    print(_run("""
        import jax
        from repro.launch.mesh import make_production_mesh
        # 8 fake devices can't fit 512; just verify axis naming contract
        try:
            make_production_mesh(multi_pod=True)
            raise SystemExit("should not fit on 8 devices")
        except ValueError:
            pass
        print("mesh contract ok")
    """))
