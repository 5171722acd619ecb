import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import seeded, weights
from chipbench.references import dense
from repro.kernels.w4a8_mm import unpack_int4
from repro.quant.serve_packed import pack_decode_params

SMOKE = dict(name="smoke", num_hidden_layers=3, hidden_size=128,
             num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=256, vocab_size=512, rms_norm_eps=1e-6,
             rope_theta=1e4, max_position_embeddings=512,
             tie_word_embeddings=True,
             datapath=dict(w_bits=4, act_bits=8, p_bits=16, tile=128))


@pytest.mark.parametrize("seed", [0, 2**31 + 9, 2**40 + 3])
def test_layer_at_a_time_build_equals_whole_model_packing(seed):
    built = weights.packed_params(SMOKE, seed)
    whole = jax.jit(lambda k: pack_decode_params(
        weights.float_params(k, SMOKE), weights.model_config(SMOKE),
        weights.ptq_config(SMOKE)))(seeded.seed_key(seed))
    a, ta = jax.tree.flatten(built)
    b, tb = jax.tree.flatten(whole)
    assert ta == tb
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)


def test_seeds_draw_different_weights():
    a = seeded.embedding(seeded.seed_key(5), SMOKE)
    b = seeded.embedding(seeded.seed_key(5 + 2**32), SMOKE)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_reference_rounds_weights_as_the_served_leaves_hold_them():
    """The reference's own 4-bit rounding gives the weights the kernel
    serves (integer codes times the f32 value of the bf16 scale), to the
    last code; it shares no code with the packer."""
    built = weights.packed_params(SMOKE, 11)
    key = seeded.seed_key(11)
    for layer in range(SMOKE["num_hidden_layers"]):
        w = seeded.layer_weights(seeded.layer_key(key, layer), SMOKE)
        for kind in ("mixer", "ffn"):
            for name, leaf in built["layers"][0][kind].items():
                k, n = w[name].shape
                codes = unpack_int4(leaf["packed"][layer])[:k, :n]
                served = codes.astype(jnp.float32) * leaf["scale"][
                    layer].astype(jnp.float32)
                ref = jax.jit(dense.rtn4)(w[name])
                np.testing.assert_array_equal(np.asarray(served),
                                              np.asarray(ref))
