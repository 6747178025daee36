"""Ahead-of-time compiles of the device path for one described TPU v5e chip.

No chip is attached: the TPU compiler compiles for a described topology
and raises what the chip's compiler would raise (an unsupported Pallas
store, a 64-bit type inside a Mosaic kernel, a program that does not fit
in device memory). Nothing runs, so these tests say nothing about results
or times; ``chip_smoke.py`` is the run on the chip.

The topology is described only inside the module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
from functools import partial

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core import jax_backend  # noqa: E402  (turns on x64, as in the app)
from repro.kernels.quorum_compare.ops import quorum_compare  # noqa: E402

HBM_BYTES = 16 * 10**9  # one TPU v5e chip
LANES = 1 << 20  # 1M-host world columns
DEPTH = 8  # queue rows
CACHE = 1024  # job-cache slots (configs/boinc_sim.py)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    total = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert total < HBM_BYTES, total
    return total


def test_x64_is_on_as_in_the_app():
    assert jax.config.jax_enable_x64


@pytest.mark.parametrize(
    "shape,dtype",
    [((1, 256), jnp.float32), ((1 << 22,), jnp.float64), ((1 << 22,), jnp.float32)],
    ids=["1x256-f32", "4M-f64", "4M-f32"],
)
def test_quorum_compare_compiles_to_mosaic(spec, shape, dtype):
    x = spec(shape, dtype)
    compiled = quorum_compare.lower(
        x, x, rtol=1e-6, atol=1e-9, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _world(spec):
    f64 = partial(spec, dtype=jnp.float64)
    return {
        "q": f64((DEPTH, LANES)),
        "mask": spec((DEPTH, LANES), jnp.bool_),
        "idx": spec((LANES,), jnp.int64),
        "lane": spec((LANES,), jnp.bool_),
        "vec": f64((LANES,)),
    }


def test_world_advance_fits_one_chip(spec):
    w = _world(spec)
    compiled = jax_backend._k_advance1.lower(
        DEPTH, w["q"], w["q"], w["q"], w["mask"], w["idx"], w["lane"], w["vec"]
    ).compile()
    _fits(compiled)


def test_world_scatter_fits_one_chip(spec):
    w = _world(spec)
    compiled = jax_backend._k_scatter.lower(
        w["q"], w["q"], w["vec"], w["idx"], w["q"], w["q"], w["vec"]
    ).compile()
    _fits(compiled)


def test_world_completed_fits_one_chip(spec):
    w = _world(spec)
    compiled = jax_backend._k_completed.lower(
        w["mask"], w["q"], w["q"], w["idx"], spec((LANES,), jnp.int64)
    ).compile()
    _fits(compiled)


@pytest.mark.parametrize("bucket", [8, 16, CACHE])
def test_dispatch_score_pass_compiles(spec, bucket):
    # the one launch a scored request makes: four host-rounded terms,
    # flop, pf and avail packed in one f64 array, padded to the bucket
    packed = spec((7, bucket), jnp.float64)
    _fits(jax_backend._k_score_est.lower(packed).compile())


@pytest.mark.parametrize("rows", [2, 3])
def test_validation_staging_fits_one_chip(spec, rows):
    # a job's results of one Mamba2-130M layer gradient (3,765,320 float32)
    # stacked on the chip, then its NaN rows flagged
    row = spec((3_765_320,), jnp.float32)
    _fits(jax_backend._k_stack_rows.lower(*[row] * rows).compile())
    mat = spec((rows, 3_765_320), jnp.float32)
    _fits(jax_backend._k_nan_rows.lower(mat).compile())
