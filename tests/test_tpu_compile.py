"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode cannot see what the TPU lowering refuses (block shapes off
the (8, 128) tiling, too much VMEM), so these tests compile the kernels at
the simulator's working shapes for a ``v5e:2x2`` topology that is
described, not attached.  Nothing runs; the compiled program must hold the
Pallas kernel (``tpu_custom_call``).  The topology is described inside a
fixture, never at import, so every test worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.metronome_fill import metronome_fill
from repro.kernels.metronome_score import metronome_score_multilink_batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of the cache entirely."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("f,l", [(8, 128), (1024, 16)])
def test_fill_kernel_compiles_at_bucket_shape(one_chip, f, l):
    """fill_corpus(bucket_shapes=True) pads every in-loop batch to 64
    problems; F and L are its power-of-two buckets."""
    b = 64
    compiled = _compile(metronome_fill, [(b, f), (b, f, l), (b, l)],
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_score_batch_kernel_compiles(one_chip):
    """(C, L, Ra, Rb) = (8, 4, 72, 72) on the Di-Pre = 72 slot circle."""
    c, l, r, s = 8, 4, 72, 72
    compiled = _compile(metronome_score_multilink_batch,
                        [(c, l, s), (c, l, r, s), (c, l, r, s), (c, l)],
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()
