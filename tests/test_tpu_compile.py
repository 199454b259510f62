"""Compile the engine's kernel paths for a described TPU v5e, no chip.

The TPU compiler ships with the installed libtpu and compiles for a chip
that is described rather than attached. These tests lower the staged
trunk forward at the shapes the engine serves and check that Mosaic
accepts the Pallas kernel (a ``tpu_custom_call`` in the compiled HLO):
what interpret mode cannot show, such as a block shape the chip's tiling
refuses. Nothing runs, so they say nothing about results or time.

The topology is described only inside a fixture: one process at a time
may load the TPU library, so it must never load while modules are
imported or collected.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_embed import fused_embed
from repro.pipeline.backend import JaxBackend, MeshJaxBackend

# the engine's shapes: power-of-two row buckets from the backend's floor
# (32) to the serving lane ceiling (engine/serve.py
# _LANE_BATCH_CANDIDATES), input width 16, and output widths spanning the
# zoo's 8-39 plus proj1d's doubling
BUCKET_ROWS = (32, 64, 128, 256, 512, 1024, 2048, 4096)
IN_DIM = 16
WIDTHS = (8, 24, 39, 80)

# the staged linear-mode forward as it runs on the chip
_RAW = functools.partial(fused_embed, block_rows=256, interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except (ImportError, RuntimeError) as e:   # no libtpu installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("rows", BUCKET_ROWS)
def test_fused_embed_compiles_for_v5e(topo, one_chip, rows, width):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    features_fn, predict_fn = JaxBackend()._compile_forward(_RAW, 1)
    x = _sds((rows, IN_DIM), one_chip)
    w = _sds((IN_DIM, width), one_chip)
    for fn in (features_fn, predict_fn):
        hlo = fn.lower(x, w).compile().as_text()
        assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("shard_rows", (8, 16))
def test_mesh_forward_compiles_on_four_v5e_chips(topo, shard_rows):
    """The 4-device shard_map forward at a shard below the 32-row bucket
    floor: the smallest buckets split this way on a 4-chip mesh."""
    mesh = Mesh(topo.devices[:4], ("data",))
    backend = MeshJaxBackend(mesh=mesh)
    assert backend.device_count == 4
    features_fn, predict_fn = backend._compile_forward(_RAW, 1)
    x = _sds((4 * shard_rows, IN_DIM), NamedSharding(mesh, P("data")))
    w = _sds((IN_DIM, 24), NamedSharding(mesh, P()))
    for fn in (features_fn, predict_fn):
        hlo = fn.lower(x, w).compile().as_text()
        assert "tpu_custom_call" in hlo
        # one program over four partitions, each chip taking its shard
        # of the rows and a whole copy of the weights
        assert "num_partitions=4" in hlo
        assert f"f32[{shard_rows},{IN_DIM}]" in hlo
        assert f"f32[{IN_DIM},24]" in hlo
