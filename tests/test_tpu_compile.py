"""The batched allocation lane compiles for a described TPU v5e chip.

Nothing runs here: XLA's TPU compiler, which is installed with jax, compiles
the lane's jitted programs for one chip of a ``v5e:2x2`` topology that is
described, not attached.  That catches what the chip's compiler would
refuse (tiling, memory, unsupported dtypes) without a chip.  The topology is
described inside a fixture, never at import, so that only the test worker
given this file loads the TPU library.
"""
import os

import pytest

pytest.importorskip("jax")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import alloc_jax  # noqa: E402

_V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # the compiler logs nowhere
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log_dir


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [(4, 128, 8), (128, 128, 512)])
def test_maxmin_batch_compiles_for_v5e(one_chip, shape):
    """The lockstep water-filling at the lane's node count, from the
    common narrow batch up to a wide one, in the lane's float64."""
    B, N, W = shape
    with jax.enable_x64(True):
        compiled = alloc_jax._build_maxmin("jnp").lower(
            _spec((B, N, W), jnp.bool_, one_chip),
            _spec((B, N, W), jnp.float64, one_chip),
            _spec((B, W), jnp.bool_, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < _V5E_HBM_BYTES


def test_lam_compiles_for_v5e(one_chip):
    """The OPT=AVG floor reduction (the Λ of every lane) in float64."""
    with jax.enable_x64(True):
        compiled = alloc_jax._lam_jit("jnp").lower(
            _spec((128, 128, 512), jnp.float64, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < _V5E_HBM_BYTES


def test_pallas_float64_refused_for_v5e(one_chip):
    """Why ``matvec="pallas"`` raises: the TPU compiler refuses any Pallas
    kernel with float64 operands, the lane's dtype, however simple its
    body.  Should a release accept it, this test fails and the Pallas
    matvec can be tried on the chip path again."""
    from jax.experimental import pallas as pl

    def double(w_ref, o_ref):
        o_ref[...] = w_ref[...] + w_ref[...]

    def call(w):
        return pl.pallas_call(
            double, out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype))(w)

    with jax.enable_x64(True):
        jax.jit(call).lower(_spec((8, 128), jnp.float32, one_chip)).compile()
        with pytest.raises(Exception, match="X64"):
            jax.jit(call).lower(
                _spec((8, 128), jnp.float64, one_chip)).compile()
