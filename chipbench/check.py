"""The output check: served tokens against the plain reference.

After the window has closed and the engine is freed, a sample of the
finished requests, drawn from the seed with the longest one always in
it, is run once through the configuration's reference
(``references/<reference>.py``) over its prompt and served tokens. For
every served token the check reads the gap by which the reference's logit
of that token lies below the reference's best logit at that position.
The number compared is the mean gap over the sample (``mean_logit_gap``):
the widest gap, printed beside it, is set by the one token whose best two
logits nearly tie under the served 8-bit activation codes, and swings
from seed to seed too far for a limit (PERF.md).

Greedy decoding only: every cell serves at temperature 0.
"""

from __future__ import annotations

import importlib

import numpy as np

#: requests compared per run: the longest finished one and five more
SAMPLE = 6
PAGE = 64


def reference(cfg: dict):
    return importlib.import_module(f"chipbench.references.{cfg['reference']}")


def pick(results: dict, prompts: dict, seed: int, n: int = SAMPLE) -> list:
    """uids of the sample: the request with the most served tokens, then
    others drawn from the seed."""
    uids = sorted(results)
    served = {u: len(results[u]) - len(prompts[u]) for u in uids}
    longest = max(uids, key=lambda u: (served[u], -u))
    rest = [u for u in uids if u != longest]
    rng = np.random.default_rng(int(seed) + 1)
    extra = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(extra)]


def batch(seqs: list, prompt_lens: list, pad_to: int):
    """Reference inputs for sequences of prompt + served tokens: (B, S)
    tokens (the last served token is never fed back), and for every served
    token its (row, position) and id."""
    S = -(-pad_to // PAGE) * PAGE
    toks = np.zeros((len(seqs), S), np.int32)
    rows, pos, target = [], [], []
    for b, (seq, s0) in enumerate(zip(seqs, prompt_lens)):
        seq = np.asarray(seq, np.int32)
        toks[b, :len(seq) - 1] = seq[:-1]
        for i in range(s0, len(seq)):
            rows.append(b)
            pos.append(i - 1)
            target.append(int(seq[i]))
    return toks, np.asarray(rows), np.asarray(pos), np.asarray(target)


def gaps(cfg: dict, seed: int, seqs: list, prompt_lens: list, pad_to: int,
         **lower) -> np.ndarray:
    """Gap of every served token below the reference's best logit. With
    ``lower`` (``act_bits=`` / ``kv_bits=``) the served tokens are instead
    the first choices of the reference computed at that precision: the
    control."""
    ref = reference(cfg)
    toks, rows, pos, target = batch(seqs, prompt_lens, pad_to)
    h = np.asarray(ref.hidden(cfg, seed, toks))[rows, pos]
    if lower:
        hl = np.asarray(ref.hidden(cfg, seed, toks, **lower))[rows, pos]
        _, target, _ = ref.head(cfg, seed, hl, target)
    best, _, served = ref.head(cfg, seed, h, target)
    return best - served
