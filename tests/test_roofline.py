"""Roofline machinery: loop-aware collective parsing + term derivation."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import TRAIN_4K, PREFILL_32K, DECODE_32K
from repro.roofline import analysis, hlo_collectives

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_loop_aware_collective_bytes_exact():
    """Ground truth: a 5-layer scan whose grad triggers one ring all-reduce
    per layer of a known size — the parser must multiply by the trip count
    and apply the ring factor exactly."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.roofline import hlo_collectives
        mesh = Mesh(np.asarray(jax.devices()[:4]), ('d',))
        def f(x, w):
            def body(h, wi):
                y = jax.lax.with_sharding_constraint(h @ wi, P('d', None))
                return y, None
            out, _ = jax.lax.scan(body, x, w)
            return out.sum()
        x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
        with jax.set_mesh(mesh):
            c = jax.jit(jax.grad(f, argnums=1),
                        in_shardings=(NamedSharding(mesh, P('d', None)),
                                      NamedSharding(mesh, P())),
                        out_shardings=NamedSharding(mesh, P())
                        ).lower(x, w).compile()
        st = hlo_collectives.analyze(c.as_text())
        # 5 iterations x (64*64*4 B) x ring factor 2*(4-1)/4
        assert st.per_kind_count['all-reduce'] == 5, st.per_kind_count
        assert abs(st.total_wire_bytes - 5 * 16384 * 1.5) < 1, \\
            st.total_wire_bytes
        print('OK')
    """)], capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_ring_factors():
    line_ar = ("%x = f32[100]{0} all-reduce(%y), "
               "replica_groups=[1,4]<=[4]")
    line_ag = ("%x = f32[400]{0} all-gather(%y), "
               "replica_groups=[1,4]<=[4]")
    st = hlo_collectives.analyze(line_ar + "\n" + line_ag)
    # all-reduce: 400B * 2 * 3/4; all-gather: 1600B * 3/4
    assert abs(st.per_kind_bytes["all-reduce"] - 600) < 1
    assert abs(st.per_kind_bytes["all-gather"] - 1200) < 1


def test_model_flops_scaling():
    cfg = registry.get("deepseek-7b")
    train = analysis.model_flops(cfg, TRAIN_4K)
    prefill = analysis.model_flops(cfg, PREFILL_32K)
    decode = analysis.model_flops(cfg, DECODE_32K)
    # train ~ 6ND on 1M tokens; prefill fwd-only on the same token count
    assert train > prefill > decode
    n_tok_train = TRAIN_4K.global_batch * TRAIN_4K.seq_len
    assert train > 6 * cfg.n_params() * n_tok_train * 0.9


def test_moe_uses_active_params():
    dense = registry.get("deepseek-7b")
    moe = registry.get("deepseek-moe-16b")
    f = analysis.model_flops(moe, TRAIN_4K)
    # 16.9B total but 2.8B active: flops must track active, not total
    assert f < 6 * moe.n_params() * TRAIN_4K.global_batch * \
        TRAIN_4K.seq_len * 0.5


def test_record_bottleneck_and_fraction():
    cfg = registry.get("deepseek-7b")
    rec = analysis.build_record(
        arch="deepseek-7b", shape=TRAIN_4K, cfg=cfg, mesh_name="16x16",
        chips=256, cost={"flops": 1e15, "bytes accessed": 1e12},
        wire_bytes=1e11, collectives={"all-reduce": 1e11})
    assert rec.bottleneck in ("compute", "memory", "collective")
    assert 0 < rec.roofline_fraction <= 1.0
    terms = {"compute": rec.t_compute, "memory": rec.t_memory,
             "collective": rec.t_collective}
    assert rec.bottleneck == max(terms, key=terms.get)


def test_memory_ledger_kimi_needs_scale_out():
    from repro.roofline.memory_ledger import build_ledger
    cfg = registry.get("kimi-k2-1t-a32b")
    par = registry.default_parallelism(cfg, TRAIN_4K)
    led = build_ledger(cfg, TRAIN_4K, par)
    # 1T params + int8 moments over 256 chips: states alone ~16 GB/chip
    assert led.params > 7e9
    assert not led.fits()
    assert led.pods_needed() >= 1


def test_memory_ledger_small_arch_fits():
    from repro.roofline.memory_ledger import build_ledger
    cfg = registry.get("internvl2-2b")
    par = registry.default_parallelism(cfg, DECODE_32K)
    led = build_ledger(cfg, DECODE_32K, par)
    assert led.fits(), led.as_dict()
