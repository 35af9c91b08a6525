"""The FLAT KNN program compiled for a described v5e chip at the published
size (1,048,576 x 128), with no chip attached (the on-chip-measurement
guide's third rehearsal): what the TPU's compiler refuses, it refuses here,
and the blocked walk must need no buffer anywhere near the (Q, capacity)
distance matrix a whole-bank top-k holds (268 MB at Q = 64).  Beside it the
two bytes kernels of the single 10 M-item filter (the benchmark's cell
bf-200c), at the plane's real size: an add must write into the donated plane
and neither may hold a second copy of it."""
import os

import jax
import jax.numpy as jnp
import pytest

from redisson_tpu.core import kernels as K

CAP, DIM, K_TOP = 1 << 20, 128, 10


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("queries,dtype,masked", [
    (1, jnp.float32, False), (64, jnp.float32, False), (4, jnp.float32, True),
    (16, jnp.int8, False),
], ids=["q1", "q64", "q4-masked", "q16-int8"])
def test_flat_topk_compiles_for_the_chip_without_the_whole_matrix(
        one_chip, queries, dtype, masked):
    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    plane = shape((CAP,), jnp.float32)
    compiled = jax.jit(K.knn_flat_topk.__wrapped__, static_argnums=(7, 8)).lower(
        shape((CAP, DIM), dtype), plane if dtype == jnp.int8 else None, plane, plane,
        plane if masked else None, shape((queries, DIM), jnp.float32),
        shape((), jnp.int32), K_TOP, "L2",
    ).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 32 << 20, stats  # never the (Q, capacity) matrix


# the single 10 M-item filter of the benchmark's cell bf-200c: 95,850,583
# cells in a plane padded to 95,851,520, items of at most 16 bytes (4 words)
BF_M, BF_CELLS, BF_K = 95_850_583, 95_851_520, 7


@pytest.mark.parametrize("bucket", [256, 8192], ids=["point", "setup-batch"])
@pytest.mark.parametrize("kernel,donated", [("bloom_add_bytes_masked", (0,)),
                                            ("bloom_contains_bytes_masked", ())])
def test_bytes_kernels_compile_for_the_chip_at_the_filters_size(one_chip, kernel, donated,
                                                                bucket):
    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    compiled = jax.jit(getattr(K, kernel).__wrapped__, static_argnums=(4, 5),
                       donate_argnums=donated).lower(
        shape((BF_CELLS,), jnp.uint8), shape((4, bucket), jnp.uint32),
        shape((bucket,), jnp.uint32), shape((), jnp.int32), BF_K, BF_M).compile()
    stats = compiled.memory_analysis()
    # no second copy of the 96 MB plane: an add writes into the donated one
    assert stats.temp_size_in_bytes < 4 << 20, stats
    assert stats.alias_size_in_bytes == (BF_CELLS if donated else 0), stats
