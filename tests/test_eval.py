"""Eval suite tests: heuristic math, artifact formats, latency harness."""
import json

import numpy as np
import yaml

from dla_tpu.data.jsonl import write_jsonl
from dla_tpu.eval.eval_alignment import load_prompts, summarize_responses


def test_summarize_responses_reference_math():
    responses = [
        "Sorry, I cannot help with that.",   # refusal
        "Here is a friendly answer.",
        "The weapon was historic.",          # toxicity proxy
        "",
    ]
    m = summarize_responses(responses)
    assert m["refusal_rate"] == 0.25
    assert m["toxicity_proxy"] == 0.25
    want_len = np.mean([6, 5, 4, 0])
    assert abs(m["avg_length"] - want_len) < 1e-9
    empty = summarize_responses([])
    assert empty == {"avg_length": 0.0, "refusal_rate": 0.0,
                     "toxicity_proxy": 0.0}


def test_load_prompts_alt_keys(tmp_path):
    write_jsonl(tmp_path / "p.jsonl", [
        {"prompt": "a"}, {"question": "b"}, {"instruction": "c"},
        {"other": "d"}])
    prompts = load_prompts({"type": "local",
                            "prompts_path": str(tmp_path / "p.jsonl")}, None)
    assert prompts == ["a", "b", "c"]
    # subsampling is deterministic per seed
    s1 = load_prompts({"type": "local",
                       "prompts_path": str(tmp_path / "p.jsonl")}, 2, seed=1)
    s2 = load_prompts({"type": "local",
                       "prompts_path": str(tmp_path / "p.jsonl")}, 2, seed=1)
    assert s1 == s2 and len(s1) == 2


def test_eval_alignment_end_to_end(tmp_path):
    from dla_tpu.eval.eval_alignment import main
    write_jsonl(tmp_path / "prompts.jsonl",
                [{"prompt": f"question {i}"} for i in range(4)])
    cfg = {
        "seed": 0,
        "models": {"base": "tiny"},
        "model": {"tokenizer": "byte"},
        "benchmarks": {
            "local_bench": {"type": "local",
                            "prompts_path": str(tmp_path / "prompts.jsonl"),
                            "max_samples": 3},
        },
        "generation": {"max_new_tokens": 4, "temperature": 0.7,
                       "top_p": 0.9, "do_sample": True, "batch_size": 2,
                       "max_prompt_length": 24},
        "logging": {"output_path": str(tmp_path / "out" / "results.json"),
                    "table_path": str(tmp_path / "out" / "summary.md")},
    }
    p = tmp_path / "eval.yaml"
    p.write_text(yaml.safe_dump(cfg))
    main(["--config", str(p)])

    results = json.loads((tmp_path / "out" / "results.json").read_text())
    assert set(results) == {"base"}
    m = results["base"]["local_bench"]
    assert set(m) == {"avg_length", "refusal_rate", "toxicity_proxy"}
    table = (tmp_path / "out" / "summary.md").read_text()
    assert table.startswith("| Model | Benchmark | Avg Len |")
    assert "| base | local_bench |" in table


def test_eval_latency_end_to_end(tmp_path):
    from dla_tpu.eval.eval_latency import main
    cfg = {
        "seed": 0,
        "models": {"tiny": "tiny"},
        "model": {"tokenizer": "byte"},
        "latency": {
            "batch_sizes": [1, 2],
            "seq_lengths": [16],
            "warmup_steps": 1,
            "measure_steps": 2,
            "decode": {"enabled": True, "batch_size": 2,
                       "prompt_length": 8, "new_tokens": 4},
        },
        "logging": {"output_path": str(tmp_path / "out" / "results.json")},
    }
    p = tmp_path / "eval.yaml"
    p.write_text(yaml.safe_dump(cfg))
    main(["--config", str(p)])
    lat = json.loads((tmp_path / "out" / "latency.json").read_text())
    # the label comes from the device the run used, not from the YAML
    assert lat["hardware"]["platform"] == "cpu"
    assert lat["hardware"]["count"] == 8
    rows = lat["tiny"]["forward"]
    assert len(rows) == 2
    assert all(r["tokens_per_second"] > 0 and r["latency_ms"] > 0
               for r in rows)
    dec = lat["tiny"]["decode"]
    assert dec["decode_tokens_per_second"] > 0


def test_eval_latency_serving_mode(tmp_path):
    """--serving runs the continuous-batching engine on a Poisson
    arrival trace and reports per-request TTFT/ITL percentiles."""
    from dla_tpu.eval.eval_latency import main
    cfg = {
        "seed": 0,
        "models": {"tiny": "tiny"},
        "model": {"tokenizer": "byte"},
        "latency": {
            "batch_sizes": [1],
            "seq_lengths": [16],
            "warmup_steps": 0,
            "measure_steps": 1,
            "decode": {"enabled": False},
            "serving": {"num_requests": 3, "arrival_rate": 200.0,
                        "new_tokens": 4, "prompt_len_min": 4,
                        "prompt_len_max": 8, "page_size": 4,
                        "num_pages": 32, "num_slots": 2,
                        "max_model_len": 32},
        },
        "logging": {"output_path": str(tmp_path / "out" / "results.json")},
    }
    p = tmp_path / "eval.yaml"
    p.write_text(yaml.safe_dump(cfg))
    main(["--config", str(p), "--serving"])
    lat = json.loads((tmp_path / "out" / "latency.json").read_text())
    srv = lat["tiny"]["serving"]
    assert srv["num_requests"] == 3
    assert srv["requests_per_second"] > 0
    for k in ("ttft_ms_p50", "ttft_ms_p95", "itl_ms_p50", "itl_ms_p95"):
        assert srv[k] >= 0.0
    assert srv["ttft_ms_p95"] >= srv["ttft_ms_p50"]
    assert srv["serve_tokens_per_second"] > 0


def test_eval_perplexity_benchmark(tmp_path):
    """benchmark type: perplexity — token-mean NLL over {prompt,response}
    pairs through the fused CE path, folded into results.json/summary.md."""
    from dla_tpu.eval.eval_alignment import main
    write_jsonl(tmp_path / "ppl.jsonl",
                [{"prompt": f"question {i}", "response": f"answer {i}"}
                 for i in range(5)])
    write_jsonl(tmp_path / "prompts.jsonl",
                [{"prompt": "hello"} for _ in range(2)])
    cfg = {
        "seed": 0,
        "models": {"base": "tiny"},
        "model": {"tokenizer": "byte"},
        "benchmarks": {
            "gen_bench": {"type": "local",
                          "prompts_path": str(tmp_path / "prompts.jsonl")},
            "heldout_ppl": {"type": "perplexity",
                            "path": str(tmp_path / "ppl.jsonl"),
                            "max_seq_length": 48},
        },
        "generation": {"max_new_tokens": 4, "batch_size": 2,
                       "max_prompt_length": 24},
        "logging": {"output_path": str(tmp_path / "out" / "results.json"),
                    "table_path": str(tmp_path / "out" / "summary.md")},
    }
    p = tmp_path / "eval.yaml"
    p.write_text(yaml.safe_dump(cfg))
    main(["--config", str(p)])

    results = json.loads((tmp_path / "out" / "results.json").read_text())
    m = results["base"]["heldout_ppl"]
    assert m["n_tokens"] > 0
    assert np.isfinite(m["nll"]) and m["perplexity"] > 1.0
    table = (tmp_path / "out" / "summary.md").read_text()
    assert "Perplexity" in table and "heldout_ppl" in table
    # the generation benchmark still renders in the heuristics table
    assert "| base | gen_bench |" in table
