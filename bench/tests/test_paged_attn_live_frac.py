"""The ``paged_attn_live_frac`` reader: on hand-built reports, on a
report from a program without the counter, and on a rehearsal run."""
import json
import os
import shutil

import pytest

import run

from test_data_driven import BURST, TINY

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Report:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _read(*reports):
    r = run.Run(cell="test", model={"family": "dense"}, layout={}, host={},
                batches=[run.Batch([], rep, None, 0.05) for rep in reports],
                memory={})
    return run._load_reader(HERE, "paged_attn_live_frac").read(r)


def test_weights_batches_by_decode_steps():
    got = _read(_Report(n_steps=30, attn_live_block_frac=0.5),
                _Report(n_steps=10, attn_live_block_frac=0.1))
    assert got == pytest.approx((30 * 0.5 + 10 * 0.1) / 40)


def test_reads_nothing_without_attention_or_counter():
    assert _read(_Report(n_steps=30, attn_live_block_frac=0.0)) is None
    assert _read(_Report(n_steps=30)) is None          # the parent program


def test_rehearse(tmp_path, capsys):
    bench_dir = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "arch"):
        (bench_dir / d).mkdir(parents=True)
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(BURST))
    shutil.copy(os.path.join(HERE, "metrics", "paged_attn_live_frac.py"),
                bench_dir / "metrics" / "paged_attn_live_frac.py")
    shutil.copy(os.path.join(HERE, "arch", TINY["arch"] + ".py"),
                bench_dir / "arch")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "test"}],
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "paged_attn_live_frac", "unit": "fraction",
                       "better": "higher", "source": "program_counter",
                       "layer": "kernels", "moves": "setup_s"}]}))
    assert run.main(["--workload", "tiny.burst", "--seed", "2147483659",
                     "--seconds", "0.1", "--rehearse"],
                    root=str(tmp_path)) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0 < out["metrics"]["paged_attn_live_frac"]["value"] <= 1
