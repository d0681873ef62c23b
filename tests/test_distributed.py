"""Multi-device semantics (8 fake CPU devices via subprocess).

The suite's main process keeps 1 device (conftest guarantee), so anything
needing a mesh runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_dp_shard_matches_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import registry
        from repro.configs.base import TRAIN_4K, ParallelismConfig
        from repro.models.model import build, make_batch
        from repro.train.optimizer import AdamW
        from repro.train.step import build_train_step
        from repro.train.dp_shard import build_dp_train_step
        from repro.train import compression

        cfg = registry.get_reduced('deepseek-7b')
        m = build(cfg)
        params = m.init(jax.random.key(0))
        opt = AdamW(lr=1e-3)
        batch = make_batch(jax.random.key(1), m, TRAIN_4K,
                           reduced_shape=(8, 16))
        # single device reference
        p1, s1 = params, opt.init(params)
        step1 = jax.jit(build_train_step(m, ParallelismConfig(), opt))
        for _ in range(3):
            p1, s1, m1 = step1(p1, s1, batch)
        # 4-way DP via shard_map
        mesh = Mesh(np.asarray(jax.devices()[:4]), ('data',))
        p2, s2 = params, opt.init(params)
        ef = compression.init_ef(params)
        step2 = jax.jit(build_dp_train_step(m, opt, mesh))
        for _ in range(3):
            p2, s2, ef, m2 = step2(p2, s2, ef, batch)
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        print('maxdiff', d)
        assert d < 5e-2, d
        print('loss1', float(m1['loss']), 'loss2', float(m2['loss']))
        assert abs(float(m1['loss']) - float(m2['loss'])) < 5e-2
    """)
    assert "maxdiff" in out


def test_compressed_dp_tracks_fp32():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import registry
        from repro.configs.base import TRAIN_4K
        from repro.models.model import build, make_batch
        from repro.train.optimizer import AdamW
        from repro.train.dp_shard import build_dp_train_step
        from repro.train import compression

        cfg = registry.get_reduced('qwen3-8b')
        m = build(cfg)
        params = m.init(jax.random.key(0))
        mesh = Mesh(np.asarray(jax.devices()[:4]), ('data',))
        opt = AdamW(lr=1e-3)
        batch = make_batch(jax.random.key(1), m, TRAIN_4K,
                           reduced_shape=(8, 16))
        losses = {}
        for comp in (False, True):
            p, s = params, opt.init(params)
            ef = compression.init_ef(params)
            step = jax.jit(build_dp_train_step(m, opt, mesh,
                                               compress_grads=comp))
            for _ in range(8):
                p, s, ef, metrics = step(p, s, ef, batch)
            losses[comp] = float(metrics['loss'])
        print(losses)
        assert abs(losses[True] - losses[False]) < 0.1
    """)


def test_pipeline_parallel_matches_stacked_scan():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.pp import pipeline_forward

        L, B, D = 8, 8, 16
        ks = jax.random.split(jax.random.key(0), 2)
        w = jax.random.normal(ks[0], (L, D, D)) * 0.3
        x = jax.random.normal(ks[1], (B, D))

        def block(wl, h):
            return jnp.tanh(h @ wl)

        def ref(w, x):
            def body(h, wl):
                return block(wl, h), None
            out, _ = jax.lax.scan(body, x, w)
            return out

        mesh = Mesh(np.asarray(jax.devices()[:4]), ('pipe',))
        out = pipeline_forward(block, w, x, mesh, microbatches=4)
        expect = ref(w, x)
        d = float(jnp.max(jnp.abs(out - expect)))
        print('pp maxdiff', d)
        assert d < 1e-5, d
    """)


def test_elastic_reshard_plan():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.elastic import make_mesh, reshard

        params = {'w': jnp.ones((16, 8)), 'b': jnp.ones((7,))}
        specs = {'w': P('data', None), 'b': P('data')}
        m8 = make_mesh(8, model_parallel=2)
        p8, plan8 = reshard(params, specs, m8)
        # b (7,) does not divide data=4 -> demoted to replication
        assert any('b' in d for d in plan8.demotions), plan8.demotions
        m4 = make_mesh(4, model_parallel=2)
        p4, plan4 = reshard(p8, specs, m4)
        np.testing.assert_array_equal(np.asarray(p4['w']),
                                      np.ones((16, 8)))
        print('elastic ok', plan4.summary())
    """)


def test_moe_ep_matches_local_dispatch():
    """Expert-parallel shard_map MoE == single-device sorted dispatch."""
    run_sub("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import registry
        from repro.configs.base import TRAIN_4K, ParallelismConfig
        from repro.distributed.sharding import make_rules, use_rules
        from repro.models.model import build, make_batch

        cfg = registry.get_reduced('deepseek-moe-16b')
        # drop-free capacity: local vs EP dispatch must then agree exactly
        # (with drops, per-shard capacity semantics legitimately differ)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=100.0))
        m = build(cfg)
        # fp32 params: distribution must be *exact* up to reduction order
        # (bf16 runs amplify ulp noise through the residual stream)
        params = m.init(jax.random.key(0), dtype=jnp.float32)
        batch = make_batch(jax.random.key(1), m, TRAIN_4K,
                           reduced_shape=(4, 16))
        batch.pop('labels')
        ref, _ = m.forward(params, batch)     # no mesh: local dispatch

        devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ('data', 'model'))
        shape = TRAIN_4K
        par = ParallelismConfig(ep=True)
        rules = make_rules(cfg, shape, par, tp_size=4, dp_size=2, mesh=mesh)
        with use_rules(rules), jax.set_mesh(mesh):
            out, _ = jax.jit(lambda p, b: m.forward(p, b))(params, batch)
        d = float(jnp.max(jnp.abs(ref - out)))
        print('moe ep maxdiff', d)
        assert d < 1e-4, d
    """)


@pytest.mark.parametrize("arch, n_held, seq", [
    ("kimi-vl-a3b", 0, 32), ("kimi-vl-a3b", 4, 32),
    ("deepseek-moe-16b", 0, 32), ("kimi-vl-a3b", 0, 512)],
    ids=["kimi-vl-a3b-0", "kimi-vl-a3b-4", "deepseek-moe-16b-0",
         "kimi-vl-a3b-0-chunks"])
def test_moe_ep_train_matches_one_device(arch, n_held, seq):
    """A MoE arch laid out as the program lays it out (experts over
    'model'): loss, step counters and every gradient equal one device's.
    The softmax router runs drop-free and without its balance loss,
    whose mean over batch shards is not the whole batch's.  At 512
    tokens a sequence each rank's held-expert loop takes several chunks
    (its carries vary over 'model' like its inputs)."""
    out = run_sub(f"""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import registry
        from repro.configs.base import TRAIN_4K
        from repro.distributed.sharding import make_rules, use_rules
        from repro.models.model import build, make_batch

        cfg = registry.get_reduced('{arch}')
        if cfg.moe.router == 'sigmoid':
            moe = dataclasses.replace(cfg.moe, n_held={n_held})
        else:
            moe = dataclasses.replace(cfg.moe, capacity_factor=100.0,
                                      aux_loss_weight=0.0)
        cfg = dataclasses.replace(cfg, moe=moe)
        m = build(cfg)
        params = m.init(jax.random.key(0), dtype=jnp.float32)
        batch = make_batch(jax.random.key(1), m, TRAIN_4K,
                           reduced_shape=(4, {seq}))
        if {seq} > 32:
            # a rank's 2 sequences: twice a chunk's rows, evenly routed,
            # on its 4 of 16 experts
            from repro.models.moe import _chunk_rows
            rows = 2 * {seq} * cfg.moe.top_k
            assert rows * 4 // 16 >= 2 * _chunk_rows(rows, 4, 16)

        def run(p, b):
            (loss, stats), g = jax.value_and_grad(
                lambda p: m.loss_and_stats(p, b), has_aux=True)(p)
            return loss, stats, g

        with jax.default_matmul_precision('highest'):
            ref = jax.jit(run)(params, batch)
            devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
            mesh = Mesh(devs, ('data', 'model'))
            par = registry.default_parallelism(cfg, TRAIN_4K)
            assert par.ep
            rules = make_rules(cfg, TRAIN_4K, par, tp_size=4, dp_size=2,
                               mesh=mesh)
            with use_rules(rules), jax.set_mesh(mesh):
                got = jax.jit(run)(params, batch)
        held = float(ref[1]['moe_held_share'])
        assert (held == 1.0) if cfg.moe.held == cfg.moe.n_experts \
            else (0.0 < held < 1.0), held
        for k in ref[1]:
            assert abs(float(got[1][k]) - float(ref[1][k])) < 1e-5, k
        assert abs(float(got[0]) - float(ref[0])) < 1e-5
        gap = max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
                  for a, b in zip(jax.tree.leaves(got[2]),
                                  jax.tree.leaves(ref[2])))
        print('moe ep grad gap', gap)
        assert gap < 1e-4, gap
    """)
    assert "moe ep grad gap" in out


def test_seq_parallel_ssd_matches_local():
    """Sequence-parallel SSD (models/ssm_sp.py): sharding S over 'model'
    with cross-rank state hand-off must reproduce the local block exactly
    (fp32)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import registry
        from repro.models import ssm as ssm_mod
        from repro.models.ssm_sp import ssm_block_seq_parallel
        from repro.models.params import init_params

        cfg = registry.get_reduced('mamba2-1.3b')
        defs = ssm_mod.ssm_defs(cfg)
        p = init_params(jax.random.key(0), defs, jnp.float32)
        B, S = 2, 64
        x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model),
                              jnp.float32) * 0.5
        ref = ssm_mod.ssm_block(p, x, cfg)
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                    ('data', 'model'))
        out = jax.jit(lambda p, x: ssm_block_seq_parallel(
            p, x, cfg, mesh, batch_axes=('data',)))(p, x)
        d = float(jnp.max(jnp.abs(ref - out)))
        print('sp-ssd maxdiff', d)
        assert d < 1e-4, d
    """)
