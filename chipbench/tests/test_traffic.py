import collections
import itertools

import numpy as np
import pytest

from chipbench import traffic


def generate(mix, seed, vocab, n):
    return list(itertools.islice(traffic.stream(mix, seed, vocab), n))

CLOSED = {"loop": "closed", "concurrency": 8, "block": 64,
          "prompt_lens": [64, 128, 256], "prompt_probs": [0.4, 0.4, 0.2],
          "output": {"median": 256, "sigma": 0.6, "min": 128, "max": 1024}}
LONG = {**CLOSED, "concurrency": 40, "prompt_lens": [1024, 1280, 1536],
        "prompt_probs": [1, 1, 1],
        "output": {"median": 384, "sigma": 0.4, "min": 256, "max": 512}}


def test_same_seed_same_requests():
    a = generate(CLOSED, 7, 1000, 150)
    b = generate(CLOSED, 7, 1000, 150)
    assert [(x.uid, x.max_new) for x in a] == [(x.uid, x.max_new) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert traffic.first_outputs(CLOSED, 7) == traffic.first_outputs(
        CLOSED, 7)


@pytest.mark.parametrize("mix", [CLOSED, LONG])
def test_seeds_share_the_work_in_another_order(mix):
    a = generate(mix, 1, 1000, 128)
    b = generate(mix, 2**40 + 1, 1000, 128)
    for key in (lambda x: x.prompt.size, lambda x: x.max_new):
        assert collections.Counter(map(key, a)) == collections.Counter(
            map(key, b))
    assert [x.prompt.size for x in a] != [x.prompt.size for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    fa = traffic.first_outputs(mix, 1)
    fb = traffic.first_outputs(mix, 2**40 + 1)
    assert sorted(fa) == sorted(fb) and fa != fb


def test_seeds_past_32_bits_differ():
    a = generate(CLOSED, 5, 1000, 8)
    b = generate(CLOSED, 5 + 2**32, 1000, 8)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_block_holds_the_mix():
    prompts, outputs = traffic.block_sizes(CLOSED)
    assert collections.Counter(prompts) == {64: 26, 128: 25, 256: 13}
    assert outputs == sorted(outputs)
    assert min(outputs) >= 128 and max(outputs) <= 1024
    assert outputs[31] < 256 < outputs[32]  # the median lies between


@pytest.mark.parametrize("mix", [CLOSED, LONG])
def test_first_outputs_follow_the_stationary_residual(mix):
    """The mean tokens left in a client's request at a random moment of
    the steady loop is E[L^2] / (2 E[L]) (+ 1/2 for whole tokens); every
    residual lies in [1, max L], one per client."""
    outs = np.asarray(traffic.block_sizes(mix)[1], np.float64)
    res = traffic.residuals(mix)
    assert len(res) == mix["concurrency"]
    assert res == sorted(res) and res[0] >= 1 and res[-1] <= outs.max()
    want = (outs ** 2).mean() / (2 * outs.mean()) + 0.5
    assert abs(np.mean(res) - want) < 0.03 * want


def test_token_ids_cover_the_vocabulary():
    items = generate(CLOSED, 3, 200064, 64)
    ids = np.concatenate([x.prompt for x in items])
    assert ids.min() >= 0 and ids.max() < 200064 and ids.max() > 190000
    assert traffic.longest_context(CLOSED) == 256 + 1024
