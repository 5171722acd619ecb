"""The served model's weights, made on the device from ``--seed`` in one
jitted call of the function its architecture module builds
(``arch/<arch>.py``, ``build_fn``)."""

from __future__ import annotations

import jax

from chipbench import arch, seeded
from repro.core import PTQConfig


def ptq_config(cfg: dict) -> PTQConfig:
    dp = cfg["datapath"]
    return PTQConfig(w_bits=dp["w_bits"], act_bits=dp["act_bits"],
                     p_bits=dp["p_bits"], tile=dp["tile"], algorithm="rtn")


def packed_params(cfg: dict, seed: int) -> dict:
    """The served tree, made on the device in one call."""
    return jax.jit(arch.of(cfg).build_fn(cfg))(seeded.seed_key(seed))
