"""A new configuration, traffic mix, per-layer metric and architecture
are new files: the harness runs a cell made of them, and ``readings.py``
reads a configuration no cell lists, with no edit of its code."""
import json
import os
import shutil

import readings
import run

TINY = {
    "name": "tiny", "source": "a throwaway test configuration",
    "reduced": [], "assumed": {}, "deployment": "none", "arch": "mha_dense",
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

# A third architecture: dense attention with grouped KV heads, two query
# heads to each (query head i reads KV head i // 2, as the program
# groups them), and a SwiGLU MLP.
TINY_GQA_ARCH = '''
import jax
import jax.numpy as jnp

import reference
import weights
from reference import HI


def _layer_params(key, m, dtype):
    d, dh, f = m["d_model"], m["d_head"], m["d_ff"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    ks = jax.random.split(key, 9)
    dense, gain = weights.dense, weights.gain
    return {"attn": {"wq": dense(ks[0], d, hq * dh, dtype),
                     "wk": dense(ks[1], d, hkv * dh, dtype),
                     "wv": dense(ks[2], d, hkv * dh, dtype),
                     "wo": dense(ks[3], hq * dh, d, dtype)},
            "mlp": {"w1": dense(ks[4], d, f, dtype),
                    "w3": dense(ks[5], d, f, dtype),
                    "w2": dense(ks[6], f, d, dtype)},
            "ln1": gain(ks[7], d, dtype), "ln2": gain(ks[8], d, dtype)}


def make_layers(key, m, dtype):
    return weights.uniform_layers(_layer_params, key, m, dtype)


def _layer(x, p, m, r, lin):
    S, dh, eps = x.shape[0], m["d_head"], m["norm_eps"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    a = p["attn"]
    h = r(reference.rms_norm(x, p["ln1"], eps))
    q = r(reference.rope(r(lin(h, a["wq"])).reshape(S, hq, dh),
                         m["rope_theta"]))
    k = r(reference.rope(r(lin(h, a["wk"])).reshape(S, hkv, dh),
                         m["rope_theta"]))
    v = r(lin(h, a["wv"])).reshape(S, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q * dh ** -0.5, k, precision=HI)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    x = r(x + r(lin(r(o).reshape(S, hq * dh), a["wo"])))
    h = r(reference.rms_norm(x, p["ln2"], eps))
    g = r(jax.nn.silu(r(lin(h, p["mlp"]["w1"]))) * r(lin(h, p["mlp"]["w3"])))
    return r(x + r(lin(g, p["mlp"]["w2"])))


def forward(x, layers, m, r, lin):
    return reference.uniform_stack(_layer, x, layers, m, r, lin)


def cim_sites(m, padded=False):
    d, dh, f = m["d_model"], m["d_head"], m["d_ff"]
    hq, hkv = m["n_heads"], m["n_kv_heads"]
    return [("qkv", d, (hq + 2 * hkv) * dh, 1.0), ("wo", hq * dh, d, 1.0),
            ("gate_up", d, 2 * f, 1.0), ("down", f, d, 1.0)] * m["n_layers"]


def kv_bytes_per_token(m, padded=False):
    return m["n_layers"] * m["n_kv_heads"] * m["d_head"] * 4


def attn_ops_rows(m, first, last, padded=False):
    if last < first:
        return 0
    ctx = (last + 1) * (last + 2) // 2 - first * (first + 1) // 2
    return 4 * m["n_layers"] * m["n_heads"] * m["d_head"] * ctx


def state_ops(m, rows):
    return 0


def prompt_multiple(m, serving):
    return 1
'''
TINY_GQA = dict(
    TINY, name="tiny_gqa", arch="tiny_gqa",
    model=dict(TINY["model"], name="tiny_gqa", n_layers=2, n_heads=4,
               n_kv_heads=2),
    check=dict(TINY["check"], sample=4, max_far_share=0.1))


def _tree(root, configs, cells, per_layer, readers=()):
    """A checkout at ``root``: BENCHMARK.json, the given configurations
    and cells under the burst mix, the given readers copied from the
    repository, and the repository's dense architecture, TINY's."""
    bench = root / "bench"
    for d in ("configs", "traffic", "metrics", "arch"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    for conf in configs:
        (bench / "configs" / f"{conf['name']}.json").write_text(
            json.dumps(conf))
    (bench / "traffic" / "burst.json").write_text(json.dumps(BURST))
    for name in readers:
        shutil.copy(os.path.join(run.BENCH_DIR, "metrics", name + ".py"),
                    bench / "metrics")
    shutil.copy(os.path.join(run.BENCH_DIR, "arch", TINY["arch"] + ".py"),
                bench / "arch")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": c["name"], "source": "test", "reduced": [],
                     "file": f"bench/configs/{c['name']}.json", "why": "test"}
                    for c in configs],
        "workloads": [{"name": f"{c}.burst", "config": c, "traffic": "burst",
                       "chips": 1, "why": "test"} for c in cells],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer}))


def _rehearse(root, cell, capsys):
    assert run.main(["--workload", cell, "--seed", str(2 ** 33 + 5),
                     "--seconds", "0.1", "--rehearse"], root=str(root)) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cell_from_new_files(tmp_path, capsys):
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "requests_served.py").write_text(READER)
    _tree(tmp_path, [TINY], ["tiny"], [
        {"name": "requests_served", "unit": "requests", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "setup_s", "workloads": ["tiny.burst"]}])
    out = _rehearse(tmp_path, "tiny.burst", capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["requests_served"] == {"value": out["attempted"],
                                                 "unit": "requests"}
    assert out["attempted"] % 6 == 0 and out["attempted"] >= 6
    assert list(out)[-1] == "checks"


def test_architecture_from_new_files(tmp_path, capsys):
    """A GQA stack: its module and its configuration are the only new
    files; the repository's kv_pool_peak_frac reader reads its pool."""
    (tmp_path / "bench" / "arch").mkdir(parents=True)
    (tmp_path / "bench" / "arch" / "tiny_gqa.py").write_text(TINY_GQA_ARCH)
    _tree(tmp_path, [TINY_GQA], ["tiny_gqa"], [
        {"name": "kv_pool_peak_frac", "unit": "fraction", "better": "lower",
         "source": "program_counter", "layer": "paged cache",
         "moves": "setup_s", "workloads": ["tiny_gqa.burst"]}],
        readers=["kv_pool_peak_frac"])
    out = _rehearse(tmp_path, "tiny_gqa.burst", capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["far_share"]["limit"] == 0.1
    assert 0 < out["metrics"]["kv_pool_peak_frac"]["value"] <= 1


def test_readings_of_a_configuration_no_cell_lists(tmp_path, capsys):
    (tmp_path / "bench" / "arch").mkdir(parents=True)
    (tmp_path / "bench" / "arch" / "tiny_gqa.py").write_text(TINY_GQA_ARCH)
    _tree(tmp_path, [TINY, TINY_GQA], ["tiny"], [])
    assert readings.main(["--config", "bench/configs/tiny_gqa.json",
                          "--traffic", "burst", "--seeds", "5",
                          "--rehearse"], root=str(tmp_path)) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["cell"] == "tiny_gqa.burst" and row["bad"] == 0
    assert row["served"]["correct"] is True
    assert "float8_e4m3fn" in row
    lines = (tmp_path / "chiprun_out" /
             "readings-tiny_gqa.burst.jsonl").read_text().splitlines()
    assert json.loads(lines[-1]) == row
