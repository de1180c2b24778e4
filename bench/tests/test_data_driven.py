"""A new configuration, traffic mix and per-layer metric are new files:
the harness runs a cell made of them with no edit of its code."""
import json

import run

TINY = {
    "name": "tiny", "source": "a throwaway test configuration",
    "reduced": [], "assumed": {}, "deployment": "none",
    "model": {"name": "tiny", "family": "dense", "n_layers": 1, "d_model": 64,
              "n_heads": 2, "n_kv_heads": 2, "d_head": 32, "d_ff": 128,
              "vocab_size": 256, "tp_head_pad": 1, "flash_block": 64},
    "serving": {"slots": 2, "prefill_chunk": 32, "block_size": 16,
                "prompt_cap": 96, "max_new_cap": 8},
    "check": {"sample": 2, "act": "bfloat16", "control": "float8_e4m3fn",
              "far_sigma": 1.0, "max_far_share": 1.0},
    "smoke": {},
}
BURST = {"queue_per_slot": 3, "prefix": {"tokens": 32, "tenants": 2, "zipf": 1.0},
         "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 48},
         "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}}
READER = '''LAYER = "scheduler"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(run):
    return sum(len(b.requests) for b in run.batches)
'''


def test_cell_from_new_files(tmp_path, capsys):
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / "bench" / d).mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (tmp_path / "bench" / "traffic" / "burst.json").write_text(json.dumps(BURST))
    (tmp_path / "bench" / "metrics" / "requests_served.py").write_text(READER)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "test"}],
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "requests_served", "unit": "requests",
                       "better": "higher", "source": "program_counter",
                       "layer": "scheduler", "moves": "setup_s",
                       "workloads": ["tiny.burst"]}]}))
    assert run.main(["--workload", "tiny.burst", "--seed", str(2 ** 33 + 5),
                     "--seconds", "0.1", "--rehearse"],
                    root=str(tmp_path)) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["requests_served"] == {"value": out["attempted"],
                                                 "unit": "requests"}
    assert out["attempted"] % 6 == 0 and out["attempted"] >= 6
    assert list(out)[-1] == "checks"
