"""Compile the served path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler builds for a topology that is described
and not attached, and refuses what the chip would refuse (block shapes
Mosaic cannot tile, gathers it cannot lower, programs larger than HBM).
Each case is a real bucket shape of the serving engine. The topology is
described inside a fixture, never at import, so every test worker collects
the same tests and only the one that runs this file loads the TPU library.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.programs import bucket_impl
from repro.kernels import neighbor_min as nm
from repro.kernels import ops
from repro.launch.roofline import chip_peaks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture
def mosaic_kernels():
    """Lower the Mosaic kernels rather than the CPU backend's interpreter."""
    prev = ops.set_interpret_mode(False)
    try:
        yield
    finally:
        ops.set_interpret_mode(prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)


@pytest.mark.parametrize("b,r,w", [(64, 64, 16), (256, 4096, 64),
                                   (4, 32768, 32), (12, 100, 4)])
@pytest.mark.parametrize("kernel", ["neighbor_min", "label_agree"])
def test_batched_kernel_lowers(kernel, b, r, w, one_chip, no_compile_cache):
    if kernel == "neighbor_min":
        fn = functools.partial(nm.neighbor_min_ell_batch, interpret=False)
        shapes = [((b, r, w), jnp.int32), ((b, r + 1), jnp.int32),
                  ((b, r + 1), jnp.bool_)]
    else:
        fn = functools.partial(nm.label_agree_ell_batch, interpret=False)
        shapes = [((b, r, w), jnp.int32), ((b, r + 1), jnp.int32)]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program,use_kernel,b,r,w,hbm_share", [
    ("pivot", False, 256, 4096, 64, 1.0),
    ("pivot", True, 256, 4096, 64, 1.0),
    ("pivot", True, 256, 32768, 32, 1.0),
    ("pivot", False, 8, 32768, 32, 1.0),
    # One power-law graph, best of 4: the ragged kernels at a wide ELL.
    ("pivot", True, 4, 16384, 1024, 1.0),
    # The LFR grid's two buckets, 16 graphs a flush, best of 4.
    ("pivot", True, 64, 1024, 64, 1.0),
    ("pivot", True, 64, 8192, 64, 1.0),
    # The smoke's precluster shapes: its O(B·R·W²) common-neighbour pass
    # must stay under half of HBM.
    ("precluster", False, 32, 1024, 64, 0.5),
    ("precluster", True, 32, 1024, 64, 0.5),
])
def test_bucket_program_compiles(program, use_kernel, b, r, w, hbm_share,
                                 topo, one_chip, no_compile_cache,
                                 mosaic_kernels):
    fn = functools.partial(bucket_impl, k=4, use_kernel=use_kernel,
                           block_rows=None, program=program,
                           objective="disagree")
    compiled = _compile(fn, one_chip, ((b, r, w), jnp.int32),
                        ((b, r + 1), jnp.int32), ((b, r + 1), jnp.bool_),
                        ((b,), jnp.int32))
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernel
    hbm = chip_peaks(topo.devices[0].device_kind).hbm_bytes
    assert _bytes(compiled) < hbm_share * hbm
