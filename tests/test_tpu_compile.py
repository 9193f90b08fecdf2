"""Compile the device path for a described TPU v5e (no chip attached).

Each program is lowered and compiled by the TPU compiler at the shapes
the main path launches; a kernel the compiler refuses fails here, not on
the chip. The Pallas kernels must reach the chip as `tpu_custom_call`s.
Nothing runs, so these say nothing about results or times.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.forest import RandomForest
from repro.fleet import BatchedRfPredictor, FleetController, JobSpec, \
    default_fleet_forest
from repro.kernels.placement_cost import _eval_jit
from repro.kernels.quantize import dequantize_pallas, quantize_pallas
from repro.kernels.rf_predict import rf_predict_pallas
from repro.kernels.waterfill import fill_rates_loop
from repro.wan.simulator import WanSimulator


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


# fleet launches (J * P * (P - 1) rows) and a 4096-row batch, with x64
# off (the sequential tick) and on (the fused tick traces under it)
@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("rows,trees,depth", [(96, 8, 5), (448, 100, 10),
                                              (4096, 100, 8)])
def test_rf_kernel_compiles(spec, rows, trees, depth, x64):
    nn = 2 ** depth - 1
    with jax.enable_x64(x64):
        compiled = jax.jit(
            lambda f, t, l, x: rf_predict_pallas(f, t, l, x, depth=depth,
                                                 interpret=False)
        ).lower(spec((trees, nn), jnp.int32),
                spec((trees, nn), jnp.float32),
                spec((trees, nn + 1), jnp.float32),
                spec((rows, 6), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# one h2o-danube-1.8b MLP weight [d_model, d_ff] as a gradient tile grid
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wire_codec_kernels_compile(spec, dtype):
    shape = (2560, 6912)
    q = jax.jit(lambda x: quantize_pallas(x, bits=8, interpret=False)
                ).lower(spec(shape, dtype)).compile()
    assert "tpu_custom_call" in q.as_text()
    grid = (shape[0] // 256, shape[1] // 256)
    dq = jax.jit(lambda a, s: dequantize_pallas(a, s, out_dtype=dtype,
                                                interpret=False)
                 ).lower(spec(shape, jnp.int8),
                         spec(grid, jnp.float32)).compile()
    assert "tpu_custom_call" in dq.as_text()


def test_water_fill_compiles(spec):
    b, n = 16, 8
    f64 = jnp.float64
    with jax.enable_x64(True):
        jax.jit(fill_rates_loop).lower(
            spec((b, n, n), f64), spec((b, n, n), f64), spec((b, n), f64),
            spec((b, n), f64), spec((n, n), f64),
            spec((b, n, n), f64)).compile()


def test_fused_tick_scan_compiles(spec):
    jobs = (JobSpec("a", dcs=(0, 1, 2, 3), priority=4.0),
            JobSpec("b", dcs=(2, 3, 4, 5), priority=2.0),
            JobSpec("c", dcs=(4, 5, 6, 7), priority=1.0))
    sim = WanSimulator(seed=0, fluct_sigma=0.0, snapshot_sigma=0.0,
                       runtime_sigma=0.0, host_sigma=0.0)
    fleet = FleetController(sim, BatchedRfPredictor(default_fleet_forest()),
                            m_total=8, jobs=jobs)
    ff = fleet.fused()
    J, P, N, T = ff.J, ff.P, ff.N, 64
    with jax.enable_x64(True):
        ff._scan_fn(detail=True).lower(
            (spec((J, P, P), jnp.int32), spec((J, P, P), jnp.float64)),
            spec((T, N, N), jnp.float64),
            spec((T, N, N), jnp.float64)).compile()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Lower the Pallas kernels that resolve `interpret` themselves as
    they would be on the chip, with no interpreted trace reused from
    (or left to) the CPU tests of this process."""
    monkeypatch.setattr("repro.kernels.rf_predict.interpret_default",
                        lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_fused_sweep_compiles(spec, compiled_kernels):
    """The vmapped sweep at the benchmark's fleet8-aws shapes (one job on
    8 DCs, a 100-tree depth-10 forest, 16 variants x 32 ticks) holds the
    RF kernel as a custom call inside the scan."""
    sim = WanSimulator(seed=0, fluct_sigma=0.0, snapshot_sigma=0.0,
                       runtime_sigma=0.0, host_sigma=0.0)
    # only the tables' shapes reach the compiler: skip the ~30-s fit
    rf = RandomForest(n_trees=100, depth=10)
    rf.feat = np.zeros((100, 1023), np.int32)
    rf.thr = np.zeros((100, 1023), np.float32)
    rf.leaf = np.ones((100, 1024), np.float32)
    fleet = FleetController(sim, BatchedRfPredictor(rf), m_total=8,
                            jobs=(JobSpec("gda", dcs=tuple(range(8))),))
    ff = fleet.fused()
    J, P, N, B, T = ff.J, ff.P, ff.N, 16, 32
    assert (J, P, N) == (1, 8, 8)
    with jax.enable_x64(True):
        compiled = ff._sweep_fn().lower(
            (spec((J, P, P), jnp.int32), spec((J, P, P), jnp.float64)),
            spec((B, T, N, N), jnp.float64),
            spec((B, T, N, N), jnp.float64)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_placement_evaluator_compiles(spec):
    m, s, n = 64, 2, 8
    f64 = jnp.float64
    with jax.enable_x64(True):
        _eval_jit.lower(
            spec((m, s, n), f64), spec((1, n, n), f64), spec((1, n), f64),
            spec((1, n), f64), spec((1, n), f64), spec((1, s + 1), f64),
            spec((1, s + 1), f64), spec((1, s + 1), f64),
            spec((), f64)).compile()

