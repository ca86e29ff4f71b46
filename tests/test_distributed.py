"""Multi-device behaviour, run in SUBPROCESSES with 8 fake host devices so the
main pytest process keeps seeing exactly one device."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, timeout=900):
    code = "import os\n" \
           "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n" \
           "from repro.launch.mesh import make_mesh\n" \
           + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_moe_ep_matches_dense():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from repro.configs.base import get_config
    from repro.models import moe as MOE
    from repro.models.dist import Dist

    cfg = get_config('deepseek_v2_236b').reduced()
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops -> exact
    mesh = make_mesh((2, 4), ('data', 'model'))
    dist = Dist(mesh=mesh, dp_axes=('data',))
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x = (jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model)) * 0.1
         ).astype(jnp.bfloat16)
    y_dense, aux_d = MOE.moe_dense(p, cfg, x)
    y_ep, aux_e = jax.jit(lambda pp, xx: MOE.moe_block(pp, cfg, xx, dist))(p, x)
    err = float(jnp.max(jnp.abs(y_ep.astype(jnp.float32) - y_dense.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(y_dense.astype(jnp.float32)))) + 1e-6
    assert err / scale < 0.05, (err, scale)
    print('moe ep vs dense OK', err / scale)
    """)


def test_train_step_on_mesh_and_elastic_restore():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, tempfile, functools
    from jax.sharding import NamedSharding
    from repro.configs.base import get_config, ShapeConfig
    from repro.models import api
    from repro.models.dist import make_dist
    from repro.models.sharding import param_shardings
    from repro import optim
    from repro.checkpoint import store

    cfg = get_config('qwen3_14b').reduced()
    model = api.build_model(cfg)
    opt = optim.make_optimizer(cfg.optimizer, total_steps=10)

    # --- train 2 steps on a 2x4 mesh
    mesh = make_mesh((2, 4), ('data', 'model'))
    dist = make_dist(mesh)
    params = model.init(jax.random.PRNGKey(0), max_seq=32)
    params = jax.device_put(params, param_shardings(params, dist))
    state = api.TrainState(params, opt.init(params))
    step = jax.jit(api.make_train_step(model, opt, dist))
    batch = {'tokens': jnp.ones((8, 32), jnp.int32),
             'labels': jnp.ones((8, 32), jnp.int32)}
    state, m = step(state, batch)
    state, m = step(state, batch)
    assert jnp.isfinite(m['loss'])
    print('mesh train OK', float(m['loss']))

    # --- checkpoint, restore onto a DIFFERENT mesh (4x2): elastic
    with tempfile.TemporaryDirectory() as d:
        store.save(d, 2, state.params)
        mesh2 = make_mesh((4, 2), ('data', 'model'))
        dist2 = make_dist(mesh2)
        shardings2 = param_shardings(state.params, dist2)
        _, params2, _ = store.restore(d, shardings=shardings2)
        # value-identical across the re-shard
        for a, b in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(params2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and trainable on the new mesh
        state2 = api.TrainState(params2, opt.init(params2))
        step2 = jax.jit(api.make_train_step(model, opt, dist2))
        state2, m2 = step2(state2, batch)
        assert jnp.isfinite(m2['loss'])
        print('elastic restore OK', float(m2['loss']))
    """)


def test_losses_match_across_mesh_shapes():
    """Same model, same data: 1-device loss == 2x4-mesh loss (SPMD correctness)."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.models import api
    from repro.models.dist import make_dist

    cfg = get_config('granite_20b').reduced()
    model = api.build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), max_seq=32)
    batch = {'tokens': jnp.arange(8 * 32).reshape(8, 32).astype(jnp.int32) % 64,
             'labels': jnp.arange(8 * 32).reshape(8, 32).astype(jnp.int32) % 64}
    loss_1dev, _ = jax.jit(lambda p, b: model.loss(p, b))(params, batch)
    mesh = make_mesh((2, 4), ('data', 'model'))
    dist = make_dist(mesh)
    loss_mesh, _ = jax.jit(lambda p, b: model.loss(p, b, dist))(params, batch)
    assert abs(float(loss_1dev) - float(loss_mesh)) < 5e-2, \
        (float(loss_1dev), float(loss_mesh))
    print('spmd loss match OK', float(loss_1dev), float(loss_mesh))
    """)


def test_compressed_crosspod_psum():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.optim import compression

    mesh = make_mesh((2, 4), ('pod', 'data'))
    g = {'w': jnp.ones((64, 8), jnp.float32) * 0.01}
    e = compression.init_residual(g)
    summed, new_e = compression.crosspod_compressed_psum(g, e, mesh, 'pod')
    np.testing.assert_allclose(np.asarray(summed['w']), 0.02, rtol=0.02)
    print('compressed psum OK')
    """)


def test_pipeline_parallel_stage_axis():
    """GPipe-style pipeline over a dedicated stage axis via shard_map +
    collective_permute; equivalence vs the unpipelined stack."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.runtime.pipeline import pipeline_apply

    S, D, n_stage, micro = 4, 16, 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (n_stage, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (16, D))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    # reference: sequential stages
    h = x
    for i in range(n_stage):
        h = stage_fn(ws[i], h)

    mesh = make_mesh((4,), ('stage',))
    out = pipeline_apply(stage_fn, ws, x, mesh, n_micro=micro)
    np.testing.assert_allclose(np.asarray(out), np.asarray(h), atol=1e-5)
    print('pipeline OK')
    """)
