"""Work and byte counts against hand counts for one layer of each
configuration, at the published (unpadded) and the program's padded
head counts."""
import json
import os

import pytest

import run
import work

CONFIGS = os.path.join(run.BENCH_DIR, "configs")


def model(name, n_layers=1):
    """The configuration's architecture module and its model section."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        conf = json.load(f)
    return (run._load_module(run.BENCH_DIR, "arch", conf["arch"]),
            dict(conf["model"], n_layers=n_layers))


MAMBA_ARCH, MAMBA = model("mamba2-130m-2l")
DENSE, MINICPM = model("minicpm-2b-1l")


def test_mamba2_layer():
    # in_proj 768 x (2*1536 + 2*128 + 24 = 3352); out_proj 1536 x 768
    assert MAMBA_ARCH.cim_sites(MAMBA) == [("in_proj", 768, 3352, 1.0),
                                           ("out_proj", 1536, 768, 1.0)]
    assert (work.cim_weights(MAMBA_ARCH, MAMBA) == 768 * 3352 + 1536 * 768
            == 3_753_984)
    assert work.cim_ops(MAMBA_ARCH, MAMBA, 1) == 7_507_968
    assert work.cim_weights(MAMBA_ARCH, MAMBA, padded=True) == 3_753_984
    # per row: 1 B in per K, 4 B out per N: 768 + 4*3352 + 1536 + 4*768
    assert work.cim_bytes(MAMBA_ARCH, MAMBA, rows=2, passes=3) == (
        3 * 3_753_984 + 2 * (768 + 13_408 + 1536 + 3072))
    # recurrence 5 * 24 heads * 64 * 128 + convs 2 * 4 * (1536 + 256)
    assert MAMBA_ARCH.state_ops(MAMBA, 1) == 983_040 + 14_336
    assert MAMBA_ARCH.kv_bytes_per_token(MAMBA) == 0


def test_minicpm_layer_unpadded():
    # 36 heads * 64 = 2304; QKV 2304 x 6912, wo 2304 x 2304,
    # gate/up 2304 x 11520, down 5760 x 2304
    assert DENSE.cim_sites(MINICPM) == [
        ("qkv", 2304, 6912, 1.0), ("wo", 2304, 2304, 1.0),
        ("gate_up", 2304, 11520, 1.0), ("down", 5760, 2304, 1.0)]
    assert work.cim_ops(DENSE, MINICPM, 1) == 2 * (
        15_925_248 + 5_308_416 + 26_542_080 + 13_271_040)
    assert work.cim_ops(DENSE, MINICPM, 1) == 122_093_568
    # K and V, 36 heads * 64, bf16
    assert DENSE.kv_bytes_per_token(MINICPM) == 9_216
    assert DENSE.kv_bytes_per_token(model("minicpm-2b-1l", 8)[1]) == 73_728


def test_minicpm_layer_padded():
    # 48 heads * 64 = 3072: QKV 2304 x 9216, wo 3072 x 2304
    assert DENSE.cim_sites(MINICPM, padded=True)[:2] == [
        ("qkv", 2304, 9216, 1.0), ("wo", 3072, 2304, 1.0)]
    assert work.cim_ops(DENSE, MINICPM, 1, padded=True) == 2 * (
        21_233_664 + 7_077_888 + 26_542_080 + 13_271_040) == 136_249_344
    assert DENSE.kv_bytes_per_token(MINICPM, padded=True) == 12_288


def test_attention_and_decode():
    # rows at positions 0..3 see contexts 1..4: 4 * 36 * 64 * 10
    assert DENSE.attn_ops_rows(MINICPM, 0, 3) == 4 * 2304 * 10
    # prompt 5, 3 served: decode rows see contexts 6 and 7
    assert work.decode_kv_bytes(DENSE, MINICPM, 5, 3) == 13 * 9_216
    assert work.decode_attn_ops(DENSE, MINICPM, 5, 3) == 4 * 2304 * 13
    assert work.decode_kv_bytes(DENSE, MINICPM, 5, 1) == 0
    assert work.request_rows(100, 10, shared_tokens=64) == 45


def test_step_work():
    i8, b16 = work.step_work(DENSE, MINICPM, [(4, 2)])
    rows = 5
    assert i8 == 122_093_568 * rows
    assert b16 == 4 * 2304 * 15 + 2 * 2304 * 122_753 * 2
    with pytest.raises(ValueError):
        DENSE.cim_sites(dict(MINICPM, n_kv_heads=4))
