"""Multi-device tests (8 forced CPU devices via subprocess: jax locks the
device count at first init, so these run out-of-process)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_distributed_search_matches_oracle():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed, match, cpq
        from repro.core import plan as plan_lib
        from repro.core.types import SearchParams
        from repro.launch import mesh as mesh_lib
        for shape, axes in [((2,4), ('data','model')), ((2,2,2), ('pod','data','model'))]:
            mesh = mesh_lib.make_mesh(shape, axes)
            rng = np.random.default_rng(0)
            data = rng.integers(0, 6, (128, 16)).astype(np.int32)
            queries = rng.integers(0, 6, (4, 16)).astype(np.int32)
            params = SearchParams(k=7, max_count=16)
            for hier in (False, True):
                plan = plan_lib.plan_search(
                    match.match_eq, 7, 16, layout=plan_lib.Layout.DISTRIBUTED,
                    mesh_axes=axes, hierarchical=hier)
                dd = jax.device_put(data, distributed.data_sharding(mesh))
                qq = jax.device_put(queries, distributed.replicated(mesh, 2))
                res = plan_lib.execute(plan, dd, qq, mesh=mesh)
                want = cpq.sort_select(match.match_eq(jnp.asarray(data), jnp.asarray(queries)), params)
                assert np.array_equal(np.asarray(res.counts), np.asarray(want.counts)), (axes, hier)
                assert np.array_equal(np.asarray(res.ids), np.asarray(want.ids)), (axes, hier)
        print('distributed search OK')
    """)


def test_sharded_train_step_matches_single_device():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch import sharding as sh_lib
        from repro.launch import mesh as sh_lib_mesh
        from repro.models.registry import get_api, get_config
        from repro.train import step as tsl
        from repro.data.pipeline import DataConfig, SyntheticTokens

        cfg = get_config('phi3-mini-3.8b-smoke')
        api = get_api(cfg)
        hp = tsl.TrainHParams(remat=False)
        batch = SyntheticTokens(cfg, DataConfig(global_batch=4, seq_len=32)).batch(0)
        loss_single = tsl.make_loss_fn(cfg, api, hp)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        l0 = float(loss_single(params, batch)[0])

        mesh = sh_lib_mesh.make_mesh((4, 2), ('data', 'model'))
        with jax.sharding.set_mesh(mesh):
            pshapes = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
            psh = sh_lib.params_shardings(pshapes, mesh, cfg.use_tp)
            bsh = sh_lib.batch_shardings({k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}, mesh, cfg.use_tp)
            pp = jax.device_put(params, psh)
            bb = {k: jax.device_put(np.asarray(v), bsh[k]) for k, v in batch.items()}
            l1 = float(jax.jit(lambda p, b: loss_single(p, b)[0], in_shardings=(psh, bsh))(pp, bb))
        assert abs(l0 - l1) < 2e-2, (l0, l1)
        print('sharded loss matches single-device:', l0, l1)
    """)


def test_elastic_restore_across_mesh_shapes(tmp_path):
    _run(f"""
        import numpy as np, jax
        from repro.checkpoint import checkpointer
        from repro.launch import sharding as sh_lib
        from repro.launch import mesh as mesh_lib
        from repro.models.registry import get_api, get_config
        from repro.train import step as tsl

        cfg = get_config('phi3-mini-3.8b-smoke')
        api = get_api(cfg)
        state = tsl.init_state(cfg, api, jax.random.PRNGKey(0), tsl.TrainHParams())
        checkpointer.save(r'{tmp_path}', 1, state, extra=dict(data_step=1))

        # restore onto a (2,4) mesh, then a (4,2) mesh: elastic reshard
        for shape in [(2, 4), (4, 2)]:
            mesh = mesh_lib.make_mesh(shape, ('data', 'model'))
            pshapes = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
            psh = sh_lib.params_shardings(pshapes, mesh, cfg.use_tp)
            ssh = sh_lib.state_shardings(jax.eval_shape(
                lambda: tsl.init_state(cfg, api, jax.random.PRNGKey(0), tsl.TrainHParams())), psh, mesh)
            restored, _ = checkpointer.restore(r'{tmp_path}', 1, state, ssh)
            for a, b in zip(jax.tree_util.tree_leaves(state.params),
                            jax.tree_util.tree_leaves(restored.params)):
                assert np.array_equal(np.asarray(a), np.asarray(b))
        print('elastic restore OK')
    """)
