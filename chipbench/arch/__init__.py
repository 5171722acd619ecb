"""Architectures, one module per kind of model: ``arch/<arch>.py``, found
by the ``arch`` a configuration file names, as its plain reference is
found by ``reference``. There is no default.

A module holds everything that depends on the model's shape, so that the
harness, the check and the metric readers read no width, head count or
layer kind from a configuration file:

- ``model_config(cfg)``: the program's ``ModelConfig``;
- ``build_fn(cfg)``: the function of a seed key that makes the served,
  packed tree (``weights.packed_params`` jits it), and
  ``float_params(key, cfg)``: the same model unpacked, whole (tests only);
- ``small(cfg)``: the configuration cut to a size a CPU test holds;
- the counts the rooflines divide by: ``head_dim(cfg)``,
  ``attn_heads(cfg)`` (query heads, KV heads), ``attn_layers(cfg)``,
  ``sites(cfg)`` ((name, K, N) of every packed matmul the model holds),
  ``matmul_params(cfg)``, ``w4a8_step(cfg, m)``,
  ``decode_token_ops(cfg, length)`` and ``prefill_ops(cfg, start, end)``.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent

_loaded: dict = {}


def of(cfg: dict, where: Path = HERE):
    """The architecture module a configuration names, loaded once per
    file from ``where``."""
    if "arch" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} names no 'arch'")
    name = cfg["arch"]
    key = (str(where), name)
    mod = _loaded.get(key)
    if mod is None:
        path = Path(where) / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(
                f"configuration {cfg.get('name')!r} names arch {name!r}; "
                f"no such module: {path}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_arch_{name.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[key] = mod
    return mod
