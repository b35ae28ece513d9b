"""Compile-only checks against a described (not attached) TPU v5e.

The TPU compiler ships with jax and compiles for a chip that is described by
name, so these tests catch what interpret mode cannot — Pallas tiles that do
not fit VMEM, ops the chip refuses — without a chip.  Nothing runs.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import jaxplan
from repro.kernels.combine import _segment_combine
from repro.kernels.partition import _partition_permute

# The Pallas PART/COMB kernels at the conformance fabric's shapes: 8 workers
# x 300 rows of width 2, 64 distinct keys over 8 destinations.
PLANE_ROWS, PLANE_WIDTH, PLANE_SEGMENTS = 2400, 2, 8 * 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_segment_combine_compiles_at_plane_shape(one_chip):
    compiled = _segment_combine.lower(
        _sds((PLANE_ROWS,), jnp.int32, one_chip),
        _sds((PLANE_ROWS, PLANE_WIDTH), jnp.float32, one_chip),
        num_segments=PLANE_SEGMENTS, block_n=256, block_d=512,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_combine_refuses_real_key_cardinality(one_chip):
    """Why the Pallas COMB cannot serve a real aggregation: its VMEM
    accumulator holds one row per (destination, key) segment, so 16
    destinations x 2,048 keys — far fewer than a TPC-H group-by has —
    already do not fit."""
    with pytest.raises(Exception, match="(?i)vmem"):
        _segment_combine.lower(
            _sds((2048,), jnp.int32, one_chip),
            _sds((2048, 4), jnp.float32, one_chip),
            num_segments=16 * 2048, block_n=256, block_d=512,
            interpret=False).compile()


def test_partition_permute_compiles_at_plane_shape(one_chip):
    compiled = _partition_permute.lower(
        _sds((PLANE_ROWS,), jnp.int32, one_chip),
        _sds((PLANE_ROWS, PLANE_WIDTH), jnp.float32, one_chip),
        num_out=PLANE_ROWS, block_in=256, block_out=256, block_d=512,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("template", ["vanilla_push", "network_aware"])
def test_replay_program_compiles_x64(one_chip, template):
    """The regular templates' jitted replay, 64-bit keys and payloads, on the
    16-worker three-level datacenter the chip smoke serves.  The 64-bit row
    arrays cross as 32-bit words: no int64 or float64 row array is a
    parameter or result of the compiled program, and each of the two is one
    1-D uint32 array (one linear layout, nothing for the host to relayout):
    the keys' bits, the payloads' float32 pairs."""
    ns, levels, rows, width = 16, 3, 4096, 4
    spec = jaxplan._PlanSpec(template=template, comb="sum", part=("hash",),
                             initial_comb=template == "network_aware",
                             ns=ns, ndst=ns, skew=False)
    shared = [((levels, ns), jnp.int32), ((levels, ns, ns), jnp.int32),
              ((levels, ns, ns), jnp.int32), ((levels,), jnp.bool_),
              ((ns, ns), jnp.int32), ((0,), jnp.int64), ((0, 1), jnp.int32),
              ((0,), jnp.int32)]
    with jax.enable_x64(True):
        fn = jaxplan._program("scan", (spec, (rows,), (rows, width),
                                       tuple(s for s, _ in shared)))
        operands = [
            jaxplan._Words(_sds((rows * 2,), jnp.uint32, one_chip),
                           (rows,), "int64", 1, False),
            jaxplan._Words(_sds((rows * width * 2,), jnp.uint32, one_chip),
                           (rows, width), "float64", width, True),
            _sds((rows,), jnp.int32, one_chip)]
        operands += [_sds(s, d, one_chip) for s, d in shared]
        compiled = fn.lower(spec, *operands).compile()
    layout = compiled.as_text().split("entry_computation_layout=")[1]
    layout = layout.split("\n")[0]
    assert f"s64[{rows}]" not in layout and f"f64[{rows}," not in layout
    keys, vals = compiled.out_info[:2]
    assert (keys.shape, keys.dtype, vals.shape, vals.dtype) == (
        (rows,), "int64", (rows, width), "float64")
    assert keys.words.shape == (2 * rows,) and keys.words.dtype == jnp.uint32
    assert vals.words.shape == (2 * rows * width,)
    assert vals.words.dtype == jnp.uint32
    assert (keys.pairs, vals.pairs) == (False, True)
