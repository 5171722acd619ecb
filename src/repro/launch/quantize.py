"""PTQ launcher: checkpoint -> calibration -> AXE quantization -> certified
quantized artifact.

    PYTHONPATH=src python -m repro.launch.quantize --arch tiny-lm-s \
        --ckpt-dir /tmp/run1 --algorithm gpfq --w-bits 4 --act-bits 8 \
        --p-bits 16 --tile 128 --out /tmp/run1_w4a8
"""

from __future__ import annotations

import argparse
import json
import os
from dataclasses import replace

import jax

from repro.checkpoint import CheckpointManager, save_pytree
from repro.compile_cache import setup_compile_cache
from repro.configs import get_config, get_smoke
from repro.core import PTQConfig
from repro.data import DataConfig, TokenBatcher
from repro.models.transformer import init_model
from repro.quant import calibrate_and_quantize
from repro.quant.pipeline import float_ppl, quantized_ppl
from repro.quant.serve_packed import export_quantized_artifact


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--algorithm", default="gpfq",
                    choices=("gpfq", "optq", "rtn", "ep_init"))
    ap.add_argument("--w-bits", type=int, default=4)
    ap.add_argument("--act-bits", type=int, default=8)
    ap.add_argument("--p-bits", type=int, default=16)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--no-constrain", action="store_true",
                    help="unconstrained Base algorithm (Table 1)")
    ap.add_argument("--sparsity", default=None, choices=("2:4",),
                    help="2:4 semi-structured weight sparsity: mask-aware "
                         "solve, certificates against the halved effective "
                         "depth (sites with K %% 4 != 0 stay dense)")
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--calib-batch-size", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default=None)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    setup_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    return quantize(cfg, args)


def quantize(cfg, args) -> dict:
    """Calibrate, quantize and certify ``cfg`` under the options ``args``
    (from :func:`build_parser`); print the JSON report, write the v2
    artifact under ``args.out/quantized`` when ``--out`` is given, and
    return the report."""
    data = TokenBatcher(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.calib_batch_size, seed=args.seed)
    )

    params = init_model(jax.random.key(args.seed), cfg)
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir)
        restored = ckpt.restore_latest({"params": params})
        if restored is None:
            raise SystemExit(f"no checkpoint under {args.ckpt_dir}")
        _, tree, _ = restored
        params = tree["params"] if "params" in tree else tree

    ptq = PTQConfig(
        w_bits=args.w_bits,
        act_bits=args.act_bits,
        p_bits=args.p_bits,
        tile=args.tile,
        algorithm=args.algorithm,
        constrain=not args.no_constrain,
        sparsity=args.sparsity,
    )
    calib = [data.batch(10_000 + i) for i in range(args.calib_batches)]
    evalb = list(data.eval_batches(args.eval_batches))

    qm = calibrate_and_quantize(params, cfg, calib, ptq)
    cert = qm.cert_summary()
    ppl_f = float_ppl(params, cfg, evalb)
    ppl_q = quantized_ppl(qm, evalb)
    report = {
        "arch": cfg.name,
        "ptq": {k: getattr(ptq, k) for k in
                ("w_bits", "act_bits", "p_bits", "tile", "algorithm", "constrain")},
        "cert": cert,
        "float_ppl": ppl_f,
        "quant_ppl": ppl_q,
        "naive_p_star_K_dmodel": ptq.naive_p_star(cfg.d_model),
        "outer_bits_K_dmodel": ptq.outer_bits(cfg.d_model),
        # exported artifacts always carry the calibrated static act
        # quantizers, so describe the datapath as served, not as configured
        "datapath": replace(
            ptq.to_datapath_spec(cfg.d_model), static_act=True
        ).describe(),
    }
    print(json.dumps(report, indent=2, default=float))

    if args.out:
        os.makedirs(args.out, exist_ok=True)
        # registry-driven: every site of every family (incl. expert-stacked
        # MoE weights) lands in the artifact under its qualified name,
        # together with its DatapathSpec (static act quantizer included),
        # corrected bias, and the equalization-folded norms/routers — the
        # versioned schema repro.launch.serve --artifact reloads
        artifact, meta = export_quantized_artifact(qm)
        save_pytree(artifact, os.path.join(args.out, "quantized"),
                    {**meta, **report})
        print(f"[quantize] artifact v{meta['artifact_version']} "
              f"({len(artifact)} leaves) -> {args.out}/quantized")
    return report


if __name__ == "__main__":
    main()
