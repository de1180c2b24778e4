"""The repository's own cells rehearse at their configurations' smoke
sizes on the CPU, through ``run.py --rehearse``: the whole path from the
configuration to the check, with ``correct`` true."""
import json

import pytest

import run


@pytest.mark.parametrize("cell", ["nemotron3-nano-7l.short_chat"])
def test_rehearsal_is_correct(cell, capsys):
    assert run.main(["--workload", cell, "--seed", str(2 ** 33 + 5),
                     "--seconds", "0.1", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert 0 < out["metrics"]["moe_live_row_frac"]["value"] < 1
