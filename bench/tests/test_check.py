"""The check catches what it must, at the smoke size on the CPU.

* The control: the reference one activation precision below its own
  (``check.control``), put in the program's place, reads above each
  cell's limit on three seeds, where the program reads below it.  On
  the CPU the program rounds its activations to bfloat16, so the smoke
  size compares against a bfloat16 reference with a float8 control; the
  chip readings at the cells' own sizes, against float32 with a
  bfloat16 control, are in PERF.md.
* A rehearsal run with the timed path broken underneath comes out not
  correct: a token altered where the decode step produces it; a decode
  step that returns its cache unchanged; half of each batch's requests
  never returned.  (Serving runs on one chip: there is no exchange
  between chips to leave out.)
"""
import json

import pytest

import check
import run

CELLS = ("mamba2-130m-2l.chat", "minicpm-2b-1l.shared_prefix")


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_program_passes(cell):
    spec = run.load_spec(run.REPO, cell, rehearse=True)
    lim = spec["config"]["check"]
    for seed in (31, 32, 33):
        c = run.Cell(spec, seed)
        batches = [c.serve(0)]
        c.free()
        _, _, gaps = c.gaps(batches, controls=(lim["control"],))
        share = {k: check.far_share(g, lim["far_sigma"])
                 for k, g in gaps.items()}
        assert check.verdict(0, share["served"], lim["max_far_share"])["correct"]
        assert not check.verdict(0, share[lim["control"]],
                                 lim["max_far_share"])["correct"]


def _rehearse(cell, capsys):
    assert run.main(["--workload", cell, "--seed", "41", "--seconds", "0.1",
                     "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_fails(cell, monkeypatch, capsys):
    from repro.models import lm
    real = lm.decode_step

    def altered(params, cfg, token, cache, live=None):
        logits, cache = real(params, cfg, token, cache, live)
        return logits.at[..., 0].add(1e4), cache   # every token becomes 0

    monkeypatch.setattr(lm, "decode_step", altered)
    out = _rehearse(cell, capsys)
    assert out["correct"] is False and out["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_unchanged_state_fails(cell, monkeypatch, capsys):
    from repro.models import lm
    real = lm.decode_step

    def frozen(params, cfg, token, cache, live=None):
        logits, _ = real(params, cfg, token, cache, live)
        return logits, cache

    monkeypatch.setattr(lm, "decode_step", frozen)
    out = _rehearse(cell, capsys)
    assert out["correct"] is False


def test_half_batch_missing_fails(monkeypatch, capsys):
    from repro.launch.scheduler import ContinuousBatchingScheduler
    real = ContinuousBatchingScheduler.run

    def half(self, requests, *a, **k):
        rep = real(self, requests, *a, **k)
        rep.finished = rep.finished[: len(rep.finished) // 2]
        return rep

    monkeypatch.setattr(ContinuousBatchingScheduler, "run", half)
    out = _rehearse(CELLS[1], capsys)
    assert out["correct"] is False and out["failed"] > 0
