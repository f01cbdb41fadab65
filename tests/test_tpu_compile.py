"""Compile rehearsals for the TPU, without a chip.

The TPU compiler is installed with JAX and compiles for a v5e that is
described rather than attached.  These tests compile the Pallas
``latency_hist`` kernel and both scan engines at the sizes
``chip_smoke.py`` runs, so a kernel the compiler refuses, or a program
that outgrows the chip's 16 GB of HBM, fails here at no chip time.
Nothing runs on a device: a compile that passes says nothing of results
or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.analytical import STATION_ORDER
from repro.core.batched_execution import _completed_latencies, _execute_batch
from repro.core.sweep import SweepSpec
from repro.core.transient import _transient_batch
from repro.kernels.latency_hist import latency_hist

HBM_BYTES = 16 * 10**9      # one v5e chip
K = len(STATION_ORDER)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (an entry compiled for a described chip cannot be read back
    without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def smoke():
    """The sizes ``chip_smoke.py`` runs at (its module constants)."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("case", ["write_only", "read_90", "ragged"])
def test_latency_hist_compiles_to_a_tpu_kernel(one_chip, smoke, case):
    """The re-tiled kernel at the execution grid's lanes x samples, and at
    a ragged shape (lanes not a multiple of 8, samples not a multiple of
    the tile): the compiler accepts it and keeps it a Pallas kernel."""
    if case == "ragged":
        lanes, samples, bins = 9, 5000, 64
    else:
        lanes = SweepSpec(**smoke.EXEC_KNOBS).size() * smoke.EXEC_SEEDS[case]
        samples = smoke.EXEC_STEPS[case] * smoke.EXEC["n_clients"]
        bins = 64
    args = (_arg((lanes, samples), jnp.float32, one_chip),
            _arg((lanes, samples), jnp.bool_, one_chip),
            _arg((lanes, bins + 1), jnp.float32, one_chip))
    compiled = jax.jit(latency_hist).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mix", ["write_only", "read_90"])
def test_execute_scan_fits_one_v5e(one_chip, smoke, mix):
    """The execution grid's scan program fits the chip, and stays under
    the bound chip_smoke.py sizes its seed count by."""
    m = SweepSpec(**smoke.EXEC_KNOBS).size()
    s = smoke.EXEC_SEEDS[mix]
    n = smoke.EXEC["n_clients"]
    ops_per_client = -(-smoke.EXEC["n_commands"] // n)
    f32, i32 = jnp.float32, jnp.int32
    args = (_arg((m, K), f32, one_chip), _arg((m, K), f32, one_chip),
            _arg((m,), i32, one_chip), _arg((m, K), i32, one_chip),
            _arg((m, s, n, ops_per_client), i32, one_chip),
            _arg((m, n), i32, one_chip), _arg((m,), f32, one_chip),
            _arg((s,), i32, one_chip))
    ma = _execute_batch.lower(*args, n_clients=n,
                              n_steps=smoke.EXEC_STEPS[mix],
                              exponential=False).compile().memory_analysis()
    total = ma.output_size_in_bytes + ma.temp_size_in_bytes
    assert total < HBM_BYTES
    assert total < smoke.EXEC_BYTES_LIMIT


@pytest.mark.parametrize("mix", ["write_only", "read_90"])
def test_completed_latencies_need_no_sample_sized_buffer(one_chip, smoke,
                                                         mix):
    """The compaction of the execution grid's samples, chunk by chunk,
    keeps no temporary the size of its ``fin`` input on the chip."""
    m = SweepSpec(**smoke.EXEC_KNOBS).size()
    s = smoke.EXEC_SEEDS[mix]
    n = smoke.EXEC["n_clients"]
    shape = (m, s, smoke.EXEC_STEPS[mix], n)
    ma = _completed_latencies.lower(
        _arg(shape, jnp.bool_, one_chip), _arg(shape, jnp.float32, one_chip),
        _arg((m, s), jnp.int32, one_chip),
        width=-(-smoke.EXEC["n_commands"] // n)).compile().memory_analysis()
    assert ma.temp_size_in_bytes < m * s * smoke.EXEC_STEPS[mix] * n


def test_transient_scan_fits_one_v5e(one_chip, smoke):
    """The transient phase's scan: five deployments x seeds x clients x
    steps in one program."""
    m, bins = 5, 96
    t = smoke.TRANSIENT
    f32, i32 = jnp.float32, jnp.int32
    args = (_arg((1, m, K), f32, one_chip), _arg((1,), i32, one_chip),
            _arg((m,), f32, one_chip), _arg((m,), i32, one_chip),
            _arg((m, K), i32, one_chip), _arg((m, bins + 1), f32, one_chip),
            _arg((t["seeds"],), i32, one_chip))
    ma = _transient_batch.lower(
        *args, n_clients=t["n_clients"], n_steps=t["n_steps"],
        warmup_steps=t["n_steps"] // 4, n_bins=bins,
        exponential=True).compile().memory_analysis()
    assert ma.output_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
