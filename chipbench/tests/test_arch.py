"""Architecture modules (``arch/<arch>.py``): found by the name a
configuration gives; a module the package does not hold served, counted
and checked through the harness by adding files alone; and the dense
module's counts as the benchmark counted before they moved there."""

import json
import re
import shutil
import time
from functools import partial

import pytest

from chipbench import arch, harness, roofline, run
from repro.quant.serve_packed import packed_weight_bytes


def test_an_unknown_arch_names_the_missing_file(tmp_path):
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(tmp_path / "nope.py"))):
        arch.of({"name": "x", "arch": "nope"}, tmp_path)
    with pytest.raises(FileNotFoundError,
                       match=re.escape(str(arch.HERE / "nope.py"))):
        arch.of({"name": "x", "arch": "nope"})
    with pytest.raises(KeyError, match="names no 'arch'"):
        arch.of({"name": "x", "reference": "dense"})


TOY = dict(name="toy", arch="toy", reference="dense", num_hidden_layers=2,
           hidden_size=128, intermediate_size=256, num_attention_heads=4,
           num_key_value_heads=2, vocab_size=512,
           max_position_embeddings=512, rope_theta=10000.0,
           rms_norm_eps=1e-6, tie_word_embeddings=True,
           datapath=dict(w_bits=4, act_bits=8, p_bits=16, tile=128),
           serving=dict(kv_dtype="int8", block_size=8))
MIX = dict(loop="closed", concurrency=4, block=8, prompt_lens=[16, 40],
           prompt_probs=[1, 1], output=dict(median=24, sigma=0.5, min=8,
                                            max=64),
           policy=dict(watermark=[0, 0]), chunk_max=8)


def test_a_new_arch_is_served_counted_and_checked_from_files_alone(
        tmp_path, monkeypatch):
    """A 2-layer dense variant whose module lies outside the package
    (``toy.py``, the dense module under another name) and a cell of data
    files: the harness builds its weights and engine through the lookup,
    serves the window and checks it against the reference, and the
    rooflines count it through the same module."""
    where = tmp_path / "arch"
    where.mkdir()
    shutil.copy(arch.HERE / "dense.py", where / "toy.py")
    monkeypatch.setattr(arch, "of", partial(arch.of, where=where))

    base = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "checks"):
        (base / sub).mkdir(parents=True)
    (base / "configs" / "toy.json").write_text(json.dumps(TOY))
    (base / "traffic" / "toy-closed.json").write_text(json.dumps(MIX))
    (base / "checks" / "toy.toy-closed.json").write_text(
        json.dumps({"mean_logit_gap": {"limit": 0.3}}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "chipbench/configs/toy.json"}],
        "workloads": [{"name": "toy.toy-closed", "config": "toy",
                       "traffic": "toy-closed", "chips": 1}],
        "end_to_end": [{"name": "decode_tok_s", "unit": "tokens/s"}],
        "per_layer": []}))

    cell = run.load_cell(tmp_path, "toy.toy-closed")
    res = harness.run(cell, seed=2**35 + 1, seconds=1.0, trace=False,
                      t_start=time.time(), root=tmp_path,
                      log=lambda *a: None)
    assert res["correct"] is True, res
    assert res["metrics"]["decode_tok_s"]["value"] > 0

    toy = arch.of(TOY)
    assert toy.__file__ == str(where / "toy.py")
    pw = packed_weight_bytes(toy.model_config(TOY))
    assert sum(roofline.w4a8_weight_bytes(k, n)
               for _, k, n in toy.sites(TOY)) == (
        pw["packed_code_bytes"] + pw["scale_bytes"] + pw["col_sums_bytes"])
    assert roofline.attn_layers(TOY) == 2
    assert roofline.w4a8_step(TOY, 4) == toy.w4a8_step(TOY, 4)
    assert roofline.decode_token_ops(TOY, 9) == toy.decode_token_ops(TOY, 9)


#: the counts ``roofline.py`` gave each configuration before they moved
#: into ``arch/dense.py``: lengths and rows as the readers pass them
PHI_SITES = [("wq", 3072, 3072), ("wk", 3072, 1024), ("wv", 3072, 1024),
             ("wo", 3072, 3072), ("wg", 3072, 8192), ("wu", 3072, 8192),
             ("wd", 8192, 3072)]
SMOL_SITES = [("wq", 960, 960), ("wk", 960, 320), ("wv", 960, 320),
              ("wo", 960, 960), ("wg", 960, 2560), ("wu", 960, 2560),
              ("wd", 2560, 960)]
BEFORE = {
    "phi4-mini-3.8b-w4a8": {
        "head_dim": 128, "attn_heads": (24, 8), "attn_layers": 32,
        "sites": PHI_SITES * 32,
        "matmul_params": 3835822080,
        "prefill_ops(0, 1024)": 6804658716672.0,
        "prefill_ops(512, 1536)": 7010817146880.0,
        "w4a8_step(64)": (412316860416.0, 1783693312.0),
        "w4a8_step(1)": (6442450944.0, 1618542592.0),
        "decode_token_ops(1)": 7672037376.0,
        "decode_token_ops(1777)": 8370388992.0,
        "kv_bytes_per_token(int8)": 2048,
        "kv_bytes_per_token(act)": 4096,
        "attn_call(int8)": (26320896.0, 4425920.0),
        "attn_call(act)": (26320896.0, 8810496.0),
    },
    "smollm-360m-w4a8": {
        "head_dim": 64, "attn_heads": (15, 5), "attn_layers": 32,
        "sites": SMOL_SITES * 32,
        "matmul_params": 361758720,
        "prefill_ops(0, 1024)": 708826890240.0,
        "prefill_ops(512, 1536)": 773251399680.0,
        "w4a8_step(64)": (40265318400.0, 226054144.0),
        "w4a8_step(1)": (629145600.0, 174444544.0),
        "decode_token_ops(1)": 723640320.0,
        "decode_token_ops(1777)": 941875200.0,
        "kv_bytes_per_token(int8)": 640,
        "kv_bytes_per_token(act)": 1280,
        "attn_call(int8)": (8225280.0, 1383800.0),
        "attn_call(act)": (8225280.0, 2753280.0),
    },
}


def _counts(cfg: dict) -> dict:
    a = arch.of(cfg)
    lens = [65, 300, 1777]
    return {
        "head_dim": a.head_dim(cfg), "attn_heads": a.attn_heads(cfg),
        "attn_layers": a.attn_layers(cfg), "sites": a.sites(cfg),
        "matmul_params": a.matmul_params(cfg),
        "prefill_ops(0, 1024)": a.prefill_ops(cfg, 0, 1024),
        "prefill_ops(512, 1536)": a.prefill_ops(cfg, 512, 1536),
        "w4a8_step(64)": roofline.w4a8_step(cfg, 64),
        "w4a8_step(1)": roofline.w4a8_step(cfg, 1),
        "decode_token_ops(1)": roofline.decode_token_ops(cfg, 1),
        "decode_token_ops(1777)": roofline.decode_token_ops(cfg, 1777),
        "kv_bytes_per_token(int8)": roofline.kv_bytes_per_token(cfg,
                                                                "int8"),
        "kv_bytes_per_token(act)": roofline.kv_bytes_per_token(cfg, "act"),
        "attn_call(int8)": roofline.attn_call(cfg, lens, "int8", 64),
        "attn_call(act)": roofline.attn_call(cfg, lens, "act", 64),
    }


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_dense_counts_are_the_counts_before_the_move(name):
    cell = run.load_cell(run.HERE.parent, {
        "phi4-mini-3.8b-w4a8": "phi4mini.decode-int8kv",
        "smollm-360m-w4a8": "smollm.longctx-int8kv"}[name])
    assert cell["cfg"]["name"] == name
    assert _counts(cell["cfg"]) == BEFORE[name]
