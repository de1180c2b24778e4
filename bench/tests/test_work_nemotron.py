"""Work and byte counts of Nemotron-3-Nano's first period (MEMEM*E) at
its published widths, against hand counts."""
from test_work import model
import work

NEMO_ARCH, NEMO = model("nemotron3-nano-7l", n_layers=7)


def test_nemotron_period():
    """MEMEM*E at published widths, 8 held experts: 62 CIM calls a pass."""
    sites = NEMO_ARCH.cim_sites(NEMO)
    assert len(sites) == 3 * 2 + 2 + 3 * (8 * 2 + 2) == 62
    # in_proj 2688 x (2*4096 + 2*8*128 + 64 = 10304), out_proj 4096 x 2688
    assert sites[:2] == [("in_proj", 2688, 10304, 1.0),
                         ("out_proj", 4096, 2688, 1.0)]
    # each held expert's up and down at the routed share 6/128
    assert sites[2] == ("expert_up", 2688, 1856, 6 / 128)
    assert sites[17:20] == [("expert_down", 1856, 2688, 6 / 128),
                            ("shared_up", 2688, 3712, 1.0),
                            ("shared_down", 3712, 2688, 1.0)]
    # QKV 32 + 2 * 2 heads of 128, wo 4096 x 2688
    assert ("qkv", 2688, 4608, 1.0) in sites and ("wo", 4096, 2688, 1.0) in sites
    m, e, a = 38_707_200, 99_778_560, 23_396_352
    assert work.cim_weights(NEMO_ARCH, NEMO) == 3 * m + 3 * e + a == 438_853_632
    # K and V of the one attention layer, 2 KV heads of 128, float32
    assert NEMO_ARCH.kv_bytes_per_token(NEMO) == 2_048
    # 3 M layers: recurrence 5 * 64 * 64 * 128, convs 2 * 4 * (4096 + 2048)
    assert NEMO_ARCH.state_ops(NEMO, 1) == 3 * (2_621_440 + 49_152)
    assert NEMO_ARCH.attn_ops_rows(NEMO, 0, 1) == 4 * 32 * 128 * 3
