"""Device-resident decode throughput: prefill and decode tok/s for the
float baseline vs the packed-dequant fallback vs the fused W4A8 kernel
datapath, through the real GenerationEngine (fused on-device loop).

Three comparisons per arch:

  * engine-level prefill + decode tok/s, float vs packed params — on this
    CPU box the packed path runs the in-graph dequant fallback; on TPU the
    same call rides the Pallas kernel (backend "auto");
  * host-loop vs fused-loop decode tok/s (the loop-overhead term the
    on-device while_loop removes);
  * site-level us/call for one decode-shaped matmul, dequant vs fused
    kernel (interpret mode on CPU — a *validity* probe, not a speed claim;
    compiled-kernel timing only means anything on TPU hardware).

Writes ``BENCH_decode.json`` (cwd) so the perf trajectory is tracked
from this PR onward, and prints the usual csv rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels.ops import quantize_activations
from repro.kernels.w4a8_mm import w4a8_decode_matmul
from repro.models.layers import dequant_weight
from repro.models.transformer import init_model
from repro.quant.serve_packed import _pack_leaf, pack_decode_params
from repro.serving import GenerationEngine, SamplerConfig

from .common import FAST, csv_row, time_min, write_bench_json

ARCHS = ["tiny-lm-xs"] if FAST else ["tiny-lm-xs", "tiny-lm-s"]
BATCH = 2 if FAST else 4
PROMPT = 8 if FAST else 32
NEW = 8 if FAST else 32
SITE_K, SITE_N = (128, 128) if FAST else (512, 512)


def _time(fn, reps: int = 5) -> float:
    return time_min(fn, reps)


def _engine_toks(gen, prompts, max_new) -> float:
    dt = _time(lambda: gen(prompts, max_new), reps=5)
    return prompts.shape[0] * max_new / dt


def _site_bench() -> dict:
    """One decode-shaped (B, K) x (K, N) site: dequant vs fused kernel."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(SITE_K, SITE_N)), jnp.float32)
    leaf = _pack_leaf(w)
    x = jnp.asarray(rng.normal(size=(BATCH, SITE_K)), jnp.float32)

    @jax.jit
    def dequant_mm(x, leaf):
        return x @ dequant_weight(leaf, x.shape[-1])

    @jax.jit
    def kernel_mm(x, leaf):
        codes, s, zp = quantize_activations(x)
        return w4a8_decode_matmul(
            codes, leaf["packed"], leaf["scale"].reshape(-1),
            leaf["col_sums"].reshape(-1), s, zp,
            interpret=jax.default_backend() != "tpu",
        )

    us_dequant = _time(lambda: jax.block_until_ready(dequant_mm(x, leaf))) * 1e6
    us_kernel = _time(lambda: jax.block_until_ready(kernel_mm(x, leaf))) * 1e6
    err = float(jnp.max(jnp.abs(dequant_mm(x, leaf) - kernel_mm(x, leaf))))
    return {"us_dequant": us_dequant, "us_kernel": us_kernel, "max_abs_err": err}


def run():
    results = {"backend": jax.default_backend(), "archs": {}}
    for arch in ARCHS:
        cfg = get_config(arch)
        params = init_model(jax.random.key(0), cfg)
        pparams = pack_decode_params(params, cfg)
        prompts = np.asarray(
            jax.random.randint(jax.random.key(1), (BATCH, PROMPT), 0, cfg.vocab),
            np.int32,
        )
        samp = SamplerConfig(temperature=0.0)
        ef = GenerationEngine(params, cfg, samp)
        ep = GenerationEngine(pparams, cfg, samp)

        row = {
            "float_fused_toks": _engine_toks(ef.generate, prompts, NEW),
            "float_host_toks": _engine_toks(ef.generate_host_loop, prompts, NEW),
            "packed_fused_toks": _engine_toks(ep.generate, prompts, NEW),
        }
        results["archs"][arch] = row
        csv_row(
            f"decode/{arch}/engine",
            1e6 * BATCH * NEW / row["packed_fused_toks"],
            f"float_fused={row['float_fused_toks']:.1f}toks;"
            f"float_host={row['float_host_toks']:.1f}toks;"
            f"packed_fused={row['packed_fused_toks']:.1f}toks",
        )

    site = _site_bench()
    results["site"] = site
    csv_row(
        "decode/site/w4a8",
        site["us_kernel"],
        f"dequant_us={site['us_dequant']:.1f};kernel_us={site['us_kernel']:.1f};"
        f"max_abs_err={site['max_abs_err']:.4f}",
    )
    write_bench_json("BENCH_decode.json", results)
    return results


if __name__ == "__main__":
    run()
