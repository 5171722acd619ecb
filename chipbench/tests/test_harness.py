"""The harness end to end on the CPU at a small size: a cell defined by
data files alone, and the check seeing a fault planted in the served
path. (The chip check lives in ``run.py`` alone.)"""

import json
import time

import pytest

from chipbench import harness, run

TINY = dict(name="tiny", reference="dense", num_hidden_layers=2,
            hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=512,
            max_position_embeddings=512, rope_theta=10000.0,
            rms_norm_eps=1e-6, tie_word_embeddings=True,
            datapath=dict(w_bits=4, act_bits=8, p_bits=16, tile=128),
            serving=dict(kv_dtype="int8", block_size=8))
MIXES = {
    "tiny-closed": dict(loop="closed", concurrency=4, block=8,
                        prompt_lens=[16, 40], prompt_probs=[1, 1],
                        output=dict(median=24, sigma=0.5, min=8, max=64),
                        policy=dict(watermark=[0, 0]), chunk_max=8),
    "tiny-wide": dict(loop="closed", concurrency=6, block=12,
                      prompt_lens=[16, 64], prompt_probs=[1, 1],
                      output=dict(median=16, sigma=0.5, min=4, max=40),
                      policy=dict(watermark=[0, 0]), chunk_max=8),
}


def make_root(tmp_path):
    """A checkout holding nothing but data files for two new cells."""
    base = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "checks"):
        (base / sub).mkdir(parents=True)
    (base / "configs" / "tiny.json").write_text(json.dumps(TINY))
    cells = []
    for mix, body in MIXES.items():
        (base / "traffic" / f"{mix}.json").write_text(json.dumps(body))
        name = f"tiny.{mix}"
        (base / "checks" / f"{name}.json").write_text(
            json.dumps({"mean_logit_gap": {"limit": 0.3}}))
        cells.append({"name": name, "config": "tiny", "traffic": mix,
                      "chips": 1, "why": "test"})
    bench = {
        "command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "chipbench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": cells,
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("decode_tok_s", "tokens/s"), ("setup_s", "s"))],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def go(root, workload, seed=2**33 + 5):
    cell = run.load_cell(root, workload)
    return harness.run(cell, seed=seed, seconds=1.5, trace=False,
                       t_start=time.time(), root=root, log=lambda *a: None)


@pytest.mark.parametrize("workload", ["tiny.tiny-closed", "tiny.tiny-wide"])
def test_a_cell_from_data_files_alone(tmp_path, workload):
    res = go(make_root(tmp_path), workload)
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"decode_tok_s", "setup_s"}
    assert res["metrics"]["decode_tok_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    c = res["check"]["mean_logit_gap"]
    assert 0 <= c["value"] <= c["limit"]


def test_the_window_opens_on_the_steady_loop(tmp_path):
    """The clients' first requests are admitted before the open; from the
    open to the close the loop stays full, and the measured span ends at
    the first stamp at or after the close."""
    from chipbench import recorder, traffic, weights

    cell = run.load_cell(make_root(tmp_path), "tiny.tiny-wide")
    cfg, mix = cell["cfg"], cell["mix"]
    engine = harness.engine_for(weights.packed_params(cfg, 3), cfg, mix, 3)
    rec = recorder.Recorder()
    done, prompts, spans, past, opened = harness.serve_window(
        engine, mix, 3, 1.0, rec)
    n = mix["concurrency"]
    first = sorted(rec.recs)[:n]
    assert rec.opened > 0 and opened is not None
    # every first request had its first token before the open
    assert all(rec.recs[u].times[0] <= rec.opened for u in first)
    assert [rec.want[u] for u in first] == traffic.first_outputs(mix, 3)
    # every request submitted later came in the window
    later = [r for u, r in rec.recs.items() if u not in first]
    assert later and all(rec.opened <= r.t_due < rec.opened + 1.0
                         for r in later)
    end = rec.span_end(1.0)
    assert end is not None and end >= rec.opened + 1.0
    assert len(done) >= 4 and set(done) <= set(prompts)


def test_a_token_altered_where_it_is_produced_fails_the_check(
        tmp_path, monkeypatch):
    import repro.serving.paged_engine as pe

    real = pe._sample_rows

    def altered(logits, temperature, keys):
        tok = real(logits, temperature, keys)
        return (tok + 1) % logits.shape[-1]

    monkeypatch.setattr(pe, "_sample_rows", altered)
    res = go(make_root(tmp_path), "tiny.tiny-closed")
    assert res["correct"] is False
    c = res["check"]["mean_logit_gap"]
    assert c["value"] > c["limit"]


def test_no_tpu_no_result(capsys):
    """On a machine without a TPU the command exits non-zero and prints
    no result line."""
    rc = run.main(["--workload", "phi4mini.decode-int8kv", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
