import json
from pathlib import Path

import pytest

from chipbench import arch, metrics, roofline
from repro.models.config import param_count
from repro.serving.scheduler import kv_page_bytes
from repro.quant.serve_packed import packed_weight_bytes

CONFIGS = sorted((Path(__file__).resolve().parents[1] / "configs").glob(
    "*.json"))


def _cfg(path):
    return json.loads(Path(path).read_text())


def _active_matmul_params(mcfg) -> int:
    """The program's count of the weights a token multiplies through: its
    active parameters less the norms' gains, and less the embedding table
    where the head is not tied to it (a lookup, no matmul)."""
    n = param_count(mcfg)["active"] - (2 * mcfg.n_layers + 1) * mcfg.d_model
    return n if mcfg.tie_embeddings else n - mcfg.vocab * mcfg.d_model


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_weight_bytes_match_the_program(path):
    cfg = _cfg(path)
    a = arch.of(cfg)
    mcfg = a.model_config(cfg)
    pw = packed_weight_bytes(mcfg)
    ours = sum(roofline.w4a8_weight_bytes(k, n) for _, k, n in a.sites(cfg))
    assert ours == (pw["packed_code_bytes"] + pw["scale_bytes"]
                    + pw["col_sums_bytes"])
    assert a.matmul_params(cfg) == _active_matmul_params(mcfg)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
@pytest.mark.parametrize("kv", ["act", "int8"])
def test_kv_bytes_match_the_program(path, kv):
    cfg = _cfg(path)
    a = arch.of(cfg)
    mcfg = a.model_config(cfg)
    bs, pages = 64, 5
    _, byt = roofline.attn_call(cfg, [pages * bs], kv, block=bs)
    q_out = 2 * 2 * a.attn_heads(cfg)[0] * a.head_dim(cfg)
    assert byt - q_out == pages * kv_page_bytes(mcfg, bs, kv) / (
        roofline.attn_layers(cfg))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_attention_shape_matches_the_program(path):
    cfg = _cfg(path)
    a = arch.of(cfg)
    mcfg = a.model_config(cfg)
    assert a.head_dim(cfg) == mcfg.head_dim
    assert a.attn_heads(cfg) == (mcfg.n_heads, mcfg.n_kv_heads)
    assert a.attn_layers(cfg) == mcfg.repeats * sum(
        s.mixer == "attn" for s in mcfg.pattern)


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
    a = arch.of(cfg)
    mcfg = a.model_config(cfg)
    head = 2.0 * mcfg.vocab * mcfg.d_model
    whole = a.prefill_ops(cfg, 0, 1024)
    parts = a.prefill_ops(cfg, 0, 512) + a.prefill_ops(cfg, 512, 1024)
    assert parts - head == pytest.approx(whole)
