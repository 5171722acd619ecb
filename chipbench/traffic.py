"""Closed-loop traffic from a mix file (``traffic/<mix>.json``) and
``--seed``.

Extends ``benchmarks/common.poisson_trace`` (seeded ``PCG64``, no wall
clock): prompt lengths from a discrete set (the admit program compiles
once per prompt length, so set-up can warm every one), log-normal output
lengths, token ids over the whole vocabulary, and N clients that each
send a new request as soon as their last one finishes.

Every seed gets the same work in another order. Requests come in blocks
of ``block``; each block holds exactly the mix's prompt-length counts and
the output lengths at the block's quantiles of the log-normal. The seed
shuffles each block and draws the token ids. So the first ``n * block``
requests of any two seeds hold the same lengths, and runs on different
seeds spread no wider than runs on one seed.

The clients' first requests are cut short to the loop's steady state
(``first_outputs``): at a random moment of a loop that has run for long,
client i's request has some tokens left, and those remaining lengths
follow the stationary residual of the output lengths. Served from there,
completions (and so admissions) come at their steady rate from the
window's first second, not in one cold wave.

A mix file::

    {"loop": "closed", "concurrency": 32, "block": 64,
     "prompt_lens": [64, 128, 256], "prompt_probs": [0.4, 0.4, 0.2],
     "output": {"median": 256, "sigma": 0.6, "min": 128, "max": 1024},
     "policy": {...},                          # SchedulerPolicy fields
     "chunk_max": 32,
     "pool_pages": 640}                        # else every row's worst case
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Item:
    uid: int
    prompt: np.ndarray  # (S0,) int32
    max_new: int


def _counts(probs, n: int) -> list[int]:
    """Largest-remainder apportionment of ``n`` over ``probs``."""
    raw = [p * n / sum(probs) for p in probs]
    out = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: out[i] - raw[i])
    for i in order[:n - sum(out)]:
        out[i] += 1
    return out


def block_sizes(mix: dict) -> tuple[list[int], list[int]]:
    """The block's prompt lengths and output lengths, unshuffled."""
    n = mix["block"]
    prompts = []
    for length, c in zip(mix["prompt_lens"],
                         _counts(mix["prompt_probs"], n)):
        prompts += [int(length)] * c
    out, nd = mix["output"], NormalDist()
    outputs = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = math.exp(math.log(out["median"]) + out["sigma"] * z)
        outputs.append(int(min(max(round(x), out["min"]), out["max"])))
    return prompts, outputs


def residuals(mix: dict) -> list[int]:
    """Tokens left, at a random moment of the steady loop, in each of the
    ``concurrency`` clients' requests, unshuffled: the quantiles of the
    output lengths' stationary residual, P(R >= r) = E[(L - r + 1)+] / E[L]
    for r = 1 .. max L."""
    outs = np.asarray(block_sizes(mix)[1], np.float64)
    r = np.arange(1, int(outs.max()) + 1)
    at_least = np.maximum(outs[None, :] - r[:, None] + 1, 0).sum(1)
    cdf = 1.0 - np.append(at_least[1:], 0.0) / outs.sum()  # P(R <= r)
    n = mix["concurrency"]
    q = (np.arange(n) + 0.5) / n
    return [int(r[min(np.searchsorted(cdf, x), r.size - 1)]) for x in q]


def stream(mix: dict, seed: int, vocab: int):
    """The mix's requests under ``seed``, without end, uids from 0."""
    rng = np.random.default_rng(int(seed))
    prompts, outputs = block_sizes(mix)
    uid = 0
    while True:
        p = rng.permutation(len(prompts))
        o = rng.permutation(len(outputs))
        for j in range(len(prompts)):
            s0 = prompts[p[j]]
            yield Item(uid=uid,
                       prompt=rng.integers(0, vocab, size=s0, dtype=np.int64)
                       .astype(np.int32),
                       max_new=outputs[o[j]])
            uid += 1


def first_outputs(mix: dict, seed: int) -> list[int]:
    """The clients' first requests' output lengths: the residuals in an
    order drawn from the seed."""
    res = residuals(mix)
    order = np.random.default_rng(int(seed) + 3).permutation(len(res))
    return [res[i] for i in order]


def longest_context(mix: dict) -> int:
    """Prompt plus output tokens of the mix's largest request."""
    return max(mix["prompt_lens"]) + mix["output"]["max"]
