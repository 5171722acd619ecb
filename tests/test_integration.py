"""End-to-end integration: train -> checkpoint -> quantize -> certify ->
serve, through the real launchers."""

import jax
import numpy as np
import pytest


@pytest.mark.slow
def test_train_quantize_serve_roundtrip(tmp_path):
    from repro.launch.quantize import main as quantize_main
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main

    ckpt = str(tmp_path / "run")
    state, losses = train_main(
        ["--arch", "tiny-lm-xs", "--steps", "80", "--batch", "8",
         "--seq", "64", "--ckpt-dir", ckpt, "--ckpt-every", "40",
         "--log-every", "40", "--lr", "1e-3"]
    )
    assert losses[-1] < losses[0]

    report = quantize_main(
        ["--arch", "tiny-lm-xs", "--ckpt-dir", ckpt, "--algorithm", "gpfq",
         "--p-bits", "16", "--tile", "64", "--calib-batches", "2",
         "--calib-batch-size", "2", "--seq", "64", "--eval-batches", "2",
         "--out", str(tmp_path / "q")]
    )
    assert report["cert"]["ok"]
    assert report["quant_ppl"] < report["float_ppl"] * 1.5
    # artifact written
    import os

    assert os.path.exists(tmp_path / "q" / "quantized" / "manifest.json")

    out = serve_main(
        ["--arch", "tiny-lm-xs", "--ckpt-dir", ckpt, "--batch", "4",
         "--prompt-len", "16", "--max-new", "8"]
    )
    assert out.shape == (4, 24)
    assert out.min() >= 0 and out.max() < 512


def test_compressed_training_step_runs():
    """int8-pod gradient compression path executes on a 1-device pod mesh."""
    from repro.configs import get_smoke
    from repro.launch.mesh import make_mesh
    from repro.runtime.sharding import axis_rules
    from repro.runtime.steps import TrainRunConfig, init_train_state, make_train_step

    cfg = get_smoke("smollm-360m").scaled(n_layers=2, vocab=64, remat="none")
    mesh = make_mesh((1, 1, 1))  # (pod, data, model)
    run = TrainRunConfig(grad_compression="int8-pod")
    state = init_train_state(jax.random.key(0), cfg, run)
    step = make_train_step(cfg, run, mesh)

    def wrapped(state, batch):
        with axis_rules(mesh):
            return step(state, batch)


    batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 16), 0, 64)}
    with jax.set_mesh(mesh):
        new_state, metrics = jax.jit(wrapped)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
