"""Multi-device tests (shard_map collectives, sharding policy, distributed
flash-decode).  These need >1 device, so each test body runs in a
subprocess with ``xla_force_host_platform_device_count`` - the main test
process keeps seeing 1 device (dry-run hygiene)."""
import json
import subprocess
import sys
import textwrap

import pytest

PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import jax, jax.numpy as jnp, numpy as np
"""


def run_sub(body: str, n_devices: int = 4, timeout: int = 480) -> str:
    code = PREAMBLE.format(n=n_devices) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_hierarchical_allreduce_matches_psum():
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.runtime.collectives import hierarchical_allreduce
    mesh = jax.make_mesh((2, 2), ("pod", "data"))
    x = jnp.arange(32, dtype=jnp.float32).reshape(4, 8)

    def mean_all(v):
        return hierarchical_allreduce(v, in_pod_axis="data",
                                      cross_pod_axis="pod")
    f = jax.jit(shard_map(mean_all, mesh=mesh,
                          in_specs=P(), out_specs=P(),
                          check_vma=False))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)
    print("OK")
    """)


def test_hierarchical_allreduce_compressed_close():
    run_sub("""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.runtime.collectives import hierarchical_allreduce
    mesh = jax.make_mesh((2, 2), ("pod", "data"))
    key = jax.random.key(0)
    x = jax.random.normal(key, (8, 16))

    def mean_c(v):
        return hierarchical_allreduce(v, in_pod_axis="data",
                                      cross_pod_axis="pod",
                                      compress_cross_pod=True)
    f = jax.jit(shard_map(mean_c, mesh=mesh, in_specs=P(),
                          out_specs=P(), check_vma=False))
    out = f(x)
    err = float(jnp.abs(out - x).max())
    scale = float(jnp.abs(x).max()) / 127.0
    assert err <= scale + 1e-6, (err, scale)
    print("OK")
    """)


def test_distributed_flash_decode_matches_ref():
    run_sub("""
    from repro.runtime.collectives import make_distributed_flash_decode
    from repro.kernels.ref import ref_decode
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    B, H, H_kv, S, D = 4, 8, 2, 64, 16
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S, H_kv, D))
    v = jax.random.normal(ks[2], (B, S, H_kv, D))
    cache_len = jnp.asarray([64, 17, 33, 5], jnp.int32)
    fn = jax.jit(make_distributed_flash_decode(mesh, seq_axis="model",
                                               batch_axes=("data",)))
    out = fn(q, k, v, cache_len)
    expect = ref_decode(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                        cache_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)
    print("OK")
    """)


def test_sharding_policy_on_small_mesh():
    """Params/batch/cache shardings must be constructible and lay out a
    smoke model on a real (2x2) mesh; one jitted train step must run."""
    run_sub("""
    import dataclasses
    from repro.configs import get_config
    from repro.runtime.sharding import ShardingPolicy
    from repro.runtime.steps import input_specs, make_train_step
    from repro.configs.shapes import ShapeSpec
    from repro.models import init_params
    from repro.optim.adamw import init_opt_state

    mesh = jax.make_mesh((2, 2), ("data", "model"))
    cfg = dataclasses.replace(get_config("granite-3-2b").smoke(),
                              n_kv_heads=2, vocab_size=128)
    policy = ShardingPolicy(cfg, mesh)
    shape = ShapeSpec("tiny", seq_len=16, global_batch=4, kind="train")
    specs = input_specs(cfg, shape)
    p_sh = policy.params_shardings(specs["params"])
    o_sh = policy.opt_state_shardings(specs["params"])
    b_sh = policy.batch_shardings(specs["batch"])
    step = jax.jit(make_train_step(cfg), in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None))
    params = init_params(cfg, jax.random.key(0))
    opt = init_opt_state(params)
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32) + 3,
             "labels": jnp.zeros((4, 16), jnp.int32) + 5}
    p2, o2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # the embedding table must actually be sharded over "model"
    emb_sh = p2["embed"]["tokens"].sharding
    assert "model" in str(emb_sh.spec), emb_sh
    print("OK", float(metrics["loss"]))
    """)


def test_zero1_shards_optimizer_state():
    run_sub("""
    import dataclasses
    from repro.configs import get_config
    from repro.runtime.sharding import ShardingPolicy
    from repro.runtime.steps import input_specs
    from repro.configs.shapes import ShapeSpec

    mesh = jax.make_mesh((2, 2), ("data", "model"))
    cfg = get_config("granite-3-2b").smoke()
    policy = ShardingPolicy(cfg, mesh, zero1=True)
    shape = ShapeSpec("tiny", seq_len=16, global_batch=4, kind="train")
    specs = input_specs(cfg, shape)
    o_sh = policy.opt_state_shardings(specs["params"])
    flat = jax.tree.leaves(o_sh["m"])
    n_data_sharded = sum("data" in str(s.spec) for s in flat)
    assert n_data_sharded > len(flat) * 0.8, \
        f"ZeRO-1 must shard most moments over data ({n_data_sharded}/{len(flat)})"
    print("OK")
    """)
