import json
from pathlib import Path

import pytest

from chipbench import metrics, roofline, weights
from repro.serving.scheduler import kv_page_bytes
from repro.quant.serve_packed import packed_weight_bytes

CONFIGS = sorted((Path(__file__).resolve().parents[1] / "configs").glob(
    "*.json"))


def _cfg(path):
    return json.loads(Path(path).read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_weight_bytes_match_the_program(path):
    cfg = _cfg(path)
    pw = packed_weight_bytes(weights.model_config(cfg))
    ours = sum(roofline.w4a8_weight_bytes(k, n)
               for _, k, n in roofline.sites(cfg)) * cfg["num_hidden_layers"]
    assert ours == (pw["packed_code_bytes"] + pw["scale_bytes"]
                    + pw["col_sums_bytes"])
    assert roofline.matmul_params(cfg) == pw["weight_elems"] + (
        cfg["vocab_size"] * cfg["hidden_size"])


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
@pytest.mark.parametrize("kv", ["act", "int8"])
def test_kv_bytes_match_the_program(path, kv):
    cfg = _cfg(path)
    mcfg = weights.model_config(cfg)
    bs, pages, L = 64, 5, cfg["num_hidden_layers"]
    d = cfg["hidden_size"]
    _, byt = roofline.attn_call(cfg, [pages * bs], kv, block=bs)
    q_out = 2 * 2 * d
    assert byt - q_out == pages * kv_page_bytes(mcfg, bs, kv) / L


def test_attention_counts_live_length_not_table_width():
    cfg = _cfg(CONFIGS[0])
    short, _ = roofline.attn_call(cfg, [10, 100], "act")
    long_, _ = roofline.attn_call(cfg, [20, 200], "act")
    assert long_ == pytest.approx(2 * short)


def test_w4a8_call_counts():
    ops, byt = roofline.w4a8_call(32, 960, 320)
    assert ops == 2 * 32 * 960 * 320
    assert byt == 1024 * 384 // 2 + 6 * 320 + 32 * 960 + 2 * 32 * 320


def test_peaks_known_and_unknown():
    p = metrics.peaks("TPU v5 lite")
    assert p["int8_ops"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        metrics.peaks("TPU v9 imaginary")


def test_prefill_ops_split_into_chunks_add_up():
    cfg = _cfg(CONFIGS[0])
    head = 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    whole = roofline.prefill_ops(cfg, 0, 1024)
    parts = roofline.prefill_ops(cfg, 0, 512) + roofline.prefill_ops(
        cfg, 512, 1024)
    assert parts - head == pytest.approx(whole)
