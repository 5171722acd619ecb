"""CPU rehearsal of ``chip_smoke.py``: its main-path phase at smoke size in
Pallas interpret mode, its refusal to run without a TPU, and where the
persistent compilation cache lands."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_path_phase_runs_in_interpret_mode(tmp_path):
    """Phase (c) end to end on smollm-360m --smoke: quantize -> v2 artifact
    -> load -> PagedEngine over float and int8 pages -> teacher-forced
    logits against the interpreted kernels and the dequant reference."""
    cs = _chip_smoke()
    res = cs.main_path_phase(
        get_smoke("smollm-360m"), "smollm-360m", str(tmp_path),
        backend="interpret", attn_impl="interpret", n_requests=4,
        prompt_len=16, max_new=4, teacher_steps=2, block_size=8,
        quantize_kw=dict(seq=32, calib_batches=1, calib_batch_size=2,
                         eval_batches=1))
    assert (tmp_path / "quantized" / "manifest.json").is_file()
    assert set(res) == {f"{kv}/{ph}/{ref}" for kv in ("act", "int8")
                        for ph in ("prefill", "decode")
                        for ref in ("interpret", "dequant")}
    # the interpreted kernels are the served path itself on the CPU
    assert all(res[k] == 0.0 for k in res if k.endswith("/interpret"))


def test_kernel_phase_in_interpret_mode():
    """Phase (b) at one SmolLM site and decode M: the W4A8 kernel and both
    paged-attention bodies within their written tolerances."""
    errs = _chip_smoke().kernel_phase(interpret=True, ms=(8,),
                                      sites=((960, 320),))
    assert set(errs) == {"w4a8 M=8 K=960 N=320", "paged_attention bf16",
                         "paged_attention int8"}


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """No accelerator (or no repo beside the script): a non-zero exit and
    no result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_compile_cache_location(monkeypatch, tmp_path):
    from repro import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # left to JAX
        monkeypatch.delenv(compile_cache.ENV_VAR)
        got = compile_cache.setup_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
