"""The served Pallas kernels compile for a TPU v5e at SmolLM-360M widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described ``v5e:2x2`` topology, which the installed TPU compiler accepts
without a chip attached. This catches what interpret mode cannot —
unaligned blocks, operand types the MXU does not take, vector layouts
Mosaic cannot lower. The topology is described inside a module fixture
(never at import), and every kernel of this check lives in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.w4a8_mm import LANE, w4a8_decode_matmul, w4a8_matmul

#: SmolLM-360M matmul sites (K, N): q/o, k/v, gate/up, down
SITES = [(960, 960), (960, 320), (960, 2560), (2560, 960)]
#: SmolLM-360M decode attention: 8 rows, 15 heads over 5 kv heads, hd 64,
#: 64-token pages, 4 pages per row
B, NH, NKV, HD, BS, P, NB = 8, 15, 5, 64, 64, 4, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed_shapes(k, n, sh):
    kp, np_ = -(-k // LANE) * LANE, -(-n // LANE) * LANE
    return (_sds((kp // 2, np_), jnp.int8, sh), _sds((n,), jnp.float32, sh),
            _sds((n,), jnp.int32, sh), _sds((), jnp.float32, sh),
            _sds((), jnp.float32, sh))


@pytest.mark.parametrize("k,n", SITES)
def test_w4a8_decode_matmul_compiles(one_chip, k, n):
    wp, sc, cs, s, zp = _packed_shapes(k, n, one_chip)
    x = _sds((8, k), jnp.uint8, one_chip)
    fn = jax.jit(lambda x, wp, sc, cs, s, zp: w4a8_decode_matmul(
        x, wp, sc, cs, s, zp, out_dtype=jnp.bfloat16))
    compiled = fn.lower(x, wp, sc, cs, s, zp).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", SITES)
def test_w4a8_matmul_compiles_at_prefill_m(one_chip, k, n):
    """A prefill-shaped M (one 128-token prompt) and a ragged one."""
    wp, sc, _, s, zp = _packed_shapes(k, n, one_chip)
    for m in (128, 130):
        x = _sds((m, k), jnp.uint8, one_chip)
        fn = jax.jit(lambda x, wp, sc, s, zp: w4a8_matmul(
            x, wp, sc, s, zp, out_dtype=jnp.bfloat16))
        fn.lower(x, wp, sc, s, zp).compile()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, kv):
    sh = one_chip
    dt = jnp.bfloat16 if kv == "bf16" else jnp.int8
    args = [_sds((B, NH, HD), jnp.bfloat16, sh),
            _sds((NB, BS, NKV, HD), dt, sh), _sds((NB, BS, NKV, HD), dt, sh),
            _sds((B, P), jnp.int32, sh), _sds((B,), jnp.int32, sh)]
    if kv == "int8":
        args += [_sds((NB, NKV), jnp.float32, sh)] * 2
        fn = jax.jit(lambda q, k, v, t, n, ks, vs: paged_decode_attention(
            q, k, v, t, n, k_scales=ks, v_scales=vs))
    else:
        fn = jax.jit(lambda q, k, v, t, n: paged_decode_attention(
            q, k, v, t, n))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_unaligned_block_is_refused():
    """A compiled kernel takes lane-aligned K / N blocks only: a smaller
    block is an error, not a silently chosen divisor."""
    x = jnp.zeros((8, 256), jnp.uint8)
    wp = jnp.zeros((128, 128), jnp.int8)
    with pytest.raises(ValueError, match="multiples of 128"):
        w4a8_matmul(x, wp, jnp.ones((128,)), 0.1, 0, block_k=64)
