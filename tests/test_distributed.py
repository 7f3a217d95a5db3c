"""Distributed pieces that need >1 device: run in a subprocess with
forced host devices (the main test process must keep 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_forked(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def test_quantize_roundtrip_bounds():
    from repro.distributed.collectives import dequantize_int8, quantize_int8
    import jax
    x = jax.random.normal(jax.random.key(0), (256,)) * 3.0
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    assert err.max() <= float(scale) / 2 + 1e-6


def test_int8_psum_with_error_feedback():
    """2-pod quantized all-reduce: mean is close; error feedback stores
    exactly what quantization dropped."""
    run_forked("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.distributed.collectives import cross_pod_grad_sync

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        g = jax.random.normal(jax.random.key(0), (2, 64))  # per-pod rows

        def f(gs, es):
            s, e = cross_pod_grad_sync({"w": gs}, {"w": es}, "pod")
            return s["w"], e["w"]

        fn = shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                       out_specs=(P("pod"), P("pod")), check_rep=False)
        synced, err = fn(g, jnp.zeros_like(g))
        true_mean = g.mean(axis=0)
        got = np.asarray(synced)[0]
        scale = float(jnp.abs(g).max()) / 127.0
        assert np.abs(got - np.asarray(true_mean)).max() <= scale, \\
            (np.abs(got - np.asarray(true_mean)).max(), scale)
        # error feedback equals what each pod's quantization dropped
        assert np.abs(np.asarray(err)).max() <= scale
        print("OK")
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    """8-device (2,4)-mesh FSDP train step == single-device step."""
    run_forked("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import reduced_config
        from repro.launch.steps import build_train_step
        from repro.training.data import SyntheticDataset
        from repro.training.optimizer import AdamWConfig, adamw_init
        from repro.training.train_step import make_train_step
        from repro.models.model import Model

        cfg = reduced_config("olmo-1b", n_layers=2, d_model=64, d_ff=128,
                             n_heads=2, kv_heads=2, head_dim=32)
        model = Model(cfg)
        ds = SyntheticDataset(vocab=cfg.vocab, seq_len=16, global_batch=8)
        batch = ds.batch_at(0)

        # single-device reference
        state0 = adamw_init(model.init(jax.random.key(0)))
        step = make_train_step(model, AdamWConfig(lr=1e-3))
        ref_state, ref_m = jax.jit(step)(state0, batch)

        # sharded execution on a (data=2, model=4) mesh
        from repro.configs.shapes import Shape
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((2, 4))
        shape = Shape("t", 16, 8, "train")
        bundle = build_train_step(cfg, shape, mesh, donate=False)
        compiled = bundle.lowered.compile()
        sh_state, sh_m = compiled(state0, batch)
        assert np.isfinite(float(sh_m["loss"]))
        np.testing.assert_allclose(float(sh_m["loss"]),
                                   float(ref_m["loss"]), rtol=1e-4)
        fr = np.concatenate([np.asarray(x, np.float32).ravel()
                             for x in jax.tree.leaves(ref_state["params"])])
        fs = np.concatenate([np.asarray(x, np.float32).ravel()
                             for x in jax.tree.leaves(sh_state["params"])])
        np.testing.assert_allclose(fs, fr, atol=1e-4, rtol=1e-3)
        print("OK")
    """)


def test_elastic_reshard_across_meshes():
    """State sharded on a (4,2) mesh restores onto (2,2) and (8,1)."""
    run_forked("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.registry import reduced_config
        from repro.distributed.fault_tolerance import elastic_reshard
        from repro.distributed.sharding import FSDP_RULES, tree_shardings
        from repro.models.model import Model
        from repro.training.optimizer import adamw_init, train_state_axes

        cfg = reduced_config("olmo-1b", n_layers=2)
        model = Model(cfg)
        params, axes = model.build(jax.random.key(0))
        state = adamw_init(params)
        st_axes = train_state_axes(axes)

        m1 = jax.make_mesh((4, 2), ("data", "model"))
        sh1 = tree_shardings(m1, FSDP_RULES, st_axes, state)
        state1 = jax.tree.map(jax.device_put, state, sh1)

        m2 = jax.make_mesh((2, 2), ("data", "model"))
        state2 = elastic_reshard(state1, st_axes, m2, FSDP_RULES)
        a = np.asarray(jax.device_get(state1["params"]["embed"]["tok"]))
        b = np.asarray(jax.device_get(state2["params"]["embed"]["tok"]))
        np.testing.assert_array_equal(a, b)
        print("OK")
    """)


def test_multipod_mesh_constructs():
    """make_production_mesh(multi_pod=True) builds (2,16,16) = 512."""
    run_forked("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh(multi_pod=True)
        assert dict(m.shape) == {"pod": 2, "data": 16, "model": 16}
        m1 = make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}
        print("OK")
    """, devices=512)
