"""The check's controls at a size a test run holds: the served kernels
(Pallas interpret mode) against the plain reference, and the reference
computed one precision step below the configuration: 4-bit activation
codes for its 8-bit codes, and 4-bit KV pages for its int8 pages. On
every seed each control's mean logit gap reads at least three times the
program's, so a limit between the two exists.
The same readings at each cell's own size come from ``control.py`` on the
chip."""

import functools

import numpy as np

from chipbench import arch, check, weights
from repro.models.layers import use_packed_backend
from repro.serving import PagedConfig, PagedEngine, SamplerConfig
from repro.serving.scheduler import Request

CFG = dict(name="tiny", arch="dense", reference="dense", num_hidden_layers=2,
           hidden_size=128, intermediate_size=256, num_attention_heads=2,
           num_key_value_heads=1, vocab_size=512,
           max_position_embeddings=512, rope_theta=1e4, rms_norm_eps=1e-6,
           tie_word_embeddings=True,
           datapath=dict(w_bits=4, act_bits=8, p_bits=16, tile=128))


@functools.cache
def served(seed: int) -> tuple:
    params = weights.packed_params(CFG, seed)
    engine = PagedEngine(
        params, arch.of(CFG).model_config(CFG),
        PagedConfig(block_size=64, num_blocks=16, max_concurrency=4,
                    max_pages_per_seq=2, attn_impl="interpret"),
        SamplerConfig(temperature=0.0))
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i, prompt=rng.integers(0, 512, size=48), max_new=32)
            for i in range(4)]
    with use_packed_backend("interpret"):
        out = engine.serve(reqs)
    return tuple(tuple(out[i]) for i in range(4))


def readings(seed: int, **lower):
    seqs = [list(s) for s in served(seed)]
    program = check.gaps(CFG, seed, seqs, [48] * 4, 128)
    control = check.gaps(CFG, seed, seqs, [48] * 4, 128, **lower)
    return float(program.mean()), float(control.mean())


def assert_far_above(**lower):
    got = [readings(s, **lower) for s in (1, 2, 3)]
    assert min(c for _, c in got) >= 3 * max(p for p, _ in got), got


def test_control_reads_far_above_the_program():
    assert_far_above(act_bits=4)


def test_kv4_control_reads_far_above_the_program():
    assert_far_above(kv_bits=4)
