"""scope_reduce: device time by the program's named scopes, host time by
its spans, and the readers built on them.

The attribution tests use a hand-built trace (times in us) of two
programs that reuse the instruction name ``fusion.1``:

  jit_other       [10, 50]   fusion.1 [20, 30]
  jit_serve_loop  [100, 200] while.1 [100, 200]
      conditional.1 [110, 140]: fusion.1 [112, 118], copy.3 [120, 122]
      conditional.1 [150, 190]: ccim_matmul_prepacked_pallas.1 [150, 170],
                                fusion.2 [172, 180], fusion.3 [182, 184]
      fusion.9 [192, 195] (not in the loop's text)

Own time: while.1 100 - 30 - 40 - 3 = 27, conditional.1 (30 - 8) + (40 -
30) = 32, so loop control 59; sched.harvest 6 + 2 (copy.3 has no
metadata and takes its computation's path) = 8; sched.step: lm.cim 20,
lm.head 8, sched.sample 2; unresolved 3; the other program 10; busy 110.
"""
import json
import os
import shutil

import pytest

import run
import scope_reduce as sr
import trace_reduce

US = 1e-6
PROBE = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")
LOOP = "jit(serve_loop)/while/body/cond"
HLO = f"""HloModule jit_serve_loop, is_scheduled=true, entry_computation_layout={{(s32[])->s32[]}}

%fused_computation (param_0: s32[]) -> s32[] {{
  %param_0 = s32[] parameter(0)
  ROOT %add.0 = s32[] add(%param_0, %param_0), metadata={{op_name="{LOOP}/branch_0_fun/sched.harvest/add"}}
}}

%branch_0_fun (p: (s32[])) -> (s32[]) {{
  %p = (s32[]) parameter(0)
  %fusion.1 = s32[] fusion(%p), kind=kLoop, calls=%fused_computation, metadata={{op_name="{LOOP}/branch_0_fun/sched.harvest/scatter"}}
  %copy.3 = s32[] copy(%fusion.1)
  ROOT %tuple.1 = (s32[]) tuple(%copy.3)
}}

%branch_3_fun (p.1: (s32[], f32[8,8]{{1,0:T(8,128)}})) -> (s32[]) {{
  %p.1 = (s32[], f32[8,8]{{1,0:T(8,128)}}) parameter(0)
  %ccim_matmul_prepacked_pallas.1 = s32[8,8]{{1,0:T(8,128)}} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="{LOOP}/branch_3_fun/sched.step/lm.step/lm.cim/jit(ccim_matmul_prepacked_pallas)/pallas_call"}}
  %fusion.2 = f32[8]{{0}} fusion(%ccim_matmul_prepacked_pallas.1), kind=kOutput, metadata={{op_name="{LOOP}/branch_3_fun/sched.step/lm.step/lm.head/dot_general"}}
  %fusion.3 = s32[8]{{0}} fusion(%fusion.2), kind=kLoop, metadata={{op_name="{LOOP}/branch_3_fun/sched.step/sched.sample/argmax"}}
  ROOT %tuple.2 = (s32[]) tuple(%fusion.3)
}}

ENTRY %main.9 (a: s32[]) -> s32[] {{
  %a = s32[] parameter(0)
  %while.1 = (s32[], f32[8,8]{{1,0}}) while(%a), condition=%cond, body=%body, metadata={{op_name="jit(serve_loop)/while"}}
  ROOT %conditional.1 = (s32[]) conditional(%a, %while.1), branch_computations={{%branch_0_fun, %branch_3_fun}}, metadata={{op_name="{LOOP}"}}
}}
"""
OPS = [("fusion.1", 20, 30), ("while.1", 100, 200),
       ("conditional.1", 110, 140), ("fusion.1", 112, 118),
       ("copy.3", 120, 122), ("conditional.1", 150, 190),
       ("ccim_matmul_prepacked_pallas.1", 150, 170), ("fusion.2", 172, 180),
       ("fusion.3", 182, 184), ("fusion.9", 192, 195)]
MODULES = [(10 * US, 50 * US, "jit_other"), (100 * US, 200 * US,
                                            "jit_serve_loop")]
HOST = [("sched.run", 0, 300), ("sched.stage", 0, 40),
        ("sched.plan_prefix", 5, 15), ("sched.carry", 40, 95),
        ("sched.loop", 95, 205), ("sched.fetch", 205, 300)]
WINDOW = (0.0, 300 * US)


def _trace():
    evs = [(n, a * US, b * US) for n, a, b in OPS]
    return sr.ScopeTrace(
        ops=[sr._in_modules(evs, MODULES)],
        host=[(n, a * US, b * US) for n, a, b in HOST],
        base=trace_reduce.Trace(ops=[evs], spans=[]))


def test_names():
    assert sr.instr_name("%fusion.12 = f32[8] fusion(%a), kind=kLoop") \
        == "fusion.12"
    assert sr.instr_name("%ccim_matmul_prepacked_skinny_pallas.1 = s32[32,"
                         "1024]{1,0:T(8,128)S(1)} custom-call(...)") \
        == "ccim_matmul_prepacked_skinny_pallas.1"
    assert sr.module_name("jit_serve_loop(7733387253206916638)") \
        == "jit_serve_loop"
    assert sr.components(f"{LOOP}/branch_3_fun/sched.step/lm.step/lm.cim/"
                         "jit(ccim_matmul_prepacked_pallas)/pallas_call") \
        == ("sched.step", "lm.step", "lm.cim")
    assert sr.components("jit(serve_loop)/while") == ()


def test_name_stacks():
    module, stacks = sr.name_stacks(HLO)
    assert module == "jit_serve_loop"
    assert stacks["while.1"] == "jit(serve_loop)/while"
    assert stacks["conditional.1"] == LOOP
    assert stacks["fusion.2"] == (f"{LOOP}/branch_3_fun/sched.step/lm.step/"
                                  "lm.head/dot_general")
    # no metadata: the path its computation's named instructions share
    assert stacks["copy.3"] == f"{LOOP}/branch_0_fun/sched.harvest/scatter"
    assert stacks["add.0"].endswith("sched.harvest/add")
    assert stacks["tuple.2"] == f"{LOOP}/branch_3_fun/sched.step"


def test_modules_keep_reused_names_apart():
    tr = _trace()
    mods = {(n, round(a / US)): m for n, m, a, _ in tr.ops[0]}
    assert mods["fusion.1", 20] == "jit_other"
    assert mods["fusion.1", 112] == "jit_serve_loop"
    own = sr.self_seconds(tr, WINDOW)
    assert own["jit_other", "fusion.1"] == pytest.approx(10 * US)
    assert own["jit_serve_loop", "fusion.1"] == pytest.approx(6 * US)
    assert own["jit_serve_loop", "while.1"] == pytest.approx(27 * US)
    assert own["jit_serve_loop", "conditional.1"] == pytest.approx(32 * US)
    assert sum(own.values()) == pytest.approx(
        trace_reduce.busy_seconds(tr.base, WINDOW))


def test_overlapping_ops_share_out_the_union():
    """Ops that overlap without nesting: the later-started one takes the
    overlap, and the own seconds still sum to the busy time."""
    evs = [("p", "m", 0.0, 100 * US), ("a", "m", 10 * US, 50 * US),
           ("b", "m", 40 * US, 60 * US), ("c", "m", 120 * US, 130 * US)]
    tr = sr.ScopeTrace(ops=[evs], host=[], base=None)
    own = sr.self_seconds(tr, (0.0, 125 * US))
    assert own == {("m", "p"): pytest.approx(50 * US),
                   ("m", "a"): pytest.approx(30 * US),
                   ("m", "b"): pytest.approx(20 * US),
                   ("m", "c"): pytest.approx(5 * US)}


def test_scope_attribution():
    tr = _trace()
    stacks, unresolved, other = sr.by_stack(sr.self_seconds(tr, WINDOW), HLO)
    assert unresolved == pytest.approx(3 * US)
    assert other == pytest.approx(10 * US)
    want = {(): 59, ("sched.harvest",): 8,
            ("sched.step", "lm.step", "lm.cim"): 20,
            ("sched.step", "lm.step", "lm.head"): 8,
            ("sched.step", "sched.sample"): 2}
    assert stacks == {k: pytest.approx(v * US) for k, v in want.items()}
    assert sr.under(stacks, "sched.step") == pytest.approx(30 * US)
    assert sr.under(stacks, "lm.cim") == pytest.approx(20 * US)
    assert sr.outside(stacks, "lm.") == pytest.approx(69 * US)
    assert {(b, l): s for b, l, s in sr.split(stacks)} == {
        ("", ""): pytest.approx(59 * US),
        ("sched.harvest", ""): pytest.approx(8 * US),
        ("sched.step", "lm.cim"): pytest.approx(20 * US),
        ("sched.step", "lm.head"): pytest.approx(8 * US),
        ("sched.step", ""): pytest.approx(2 * US)}
    # a window that ends inside the second iteration cuts its ops
    part, _, _ = sr.by_stack(sr.self_seconds(tr, (0.0, 175 * US)), HLO)
    assert sr.under(part, "lm.cim") == pytest.approx(20 * US)
    assert sr.under(part, "lm.head") == pytest.approx(3 * US)
    # while.1 75 - 30 - 25, conditional.1 22 + (25 - 23)
    assert part[()] == pytest.approx(44 * US)


def test_program_span_attribution():
    tr = _trace()
    host = sr.host_by_span(tr, WINDOW)
    assert host == {k: pytest.approx(v * US) for k, v in {
        "sched.run": 300, "sched.stage": 40, "sched.plan_prefix": 10,
        "sched.carry": 55, "sched.loop": 110, "sched.fetch": 95}.items()}
    # busy [20, 30] and [100, 200]: gaps [0, 20] (mid 10: plan_prefix),
    # [30, 100] (mid 65: carry), [200, 300] (mid 250: fetch)
    assert dict(sr.idle_by_span(tr, WINDOW)) == {
        "sched.plan_prefix": pytest.approx(20 * US),
        "sched.carry": pytest.approx(70 * US),
        "sched.fetch": pytest.approx(100 * US)}


def test_load_recorded_trace():
    tr = sr.load(PROBE)
    base = trace_reduce.load(PROBE)
    # the same ops at the same (aligned) times, with whole names
    assert [(a, b) for _, _, a, b in tr.ops[0]] == [
        (a, b) for _, a, b in base.ops[0]]
    assert [trace_reduce.op_name(n) for n, _, _, _ in tr.ops[0]] == [
        n for n, _, _ in base.ops[0]]
    assert {m for _, m, _, _ in tr.ops[0]} == {"jit__lambda"}
    assert "ccim_matmul_prepacked_skinny_pallas.1" in [
        n for n, _, _, _ in tr.ops[0]]
    assert tr.host == []          # the probe holds bench.* spans only


class _Report:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _run(batches, **extra):
    r = run.Run(cell="test", model={"family": "dense"}, layout={},
                host={}, batches=batches, memory={}, window=WINDOW, **extra)
    return r


def _reader(name):
    return run._load_reader(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), name)


def test_readers_on_a_hand_built_run():
    rep = _Report(n_steps=2, n_pf=0, n_compiles=0,
                  host_s={"sched.run": 0.05, "sched.loop": 0.04})
    r = _run([run.Batch([], rep, None, 0.05)], trace=_trace().base)
    r.scope_trace, r.loop_text = _trace(), HLO
    assert _reader("decode_step_ms").read(r) == pytest.approx(1e3 * 30e-6 / 2)
    assert _reader("prefill_chunk_ms").read(r) is None       # no chunks
    assert _reader("sched_device_frac").read(r) == pytest.approx(69 / 110)
    assert _reader("host_overhead_s").read(r) == pytest.approx(0.01)
    assert _reader("window_compiles").read(r) == 0


def test_readers_read_nothing_from_the_parent_program():
    """A report without the new fields, a run without the scope trace:
    every new reader returns None, none raises."""
    rep = _Report(n_steps=2, n_pf=1)
    r = _run([run.Batch([], rep, None, 0.05)])
    for name in ("decode_step_ms", "prefill_chunk_ms", "sched_device_frac",
                 "host_overhead_s", "window_compiles"):
        assert _reader(name).read(r) is None, name


def test_readers_rehearse(tmp_path, capsys):
    """The five readers on a rehearsal run at smoke size, no chip: the
    program's counters read numbers, the device readers nothing."""
    from test_data_driven import BURST, TINY
    bench_dir = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "arch"):
        (bench_dir / d).mkdir(parents=True)
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(BURST))
    names = ("decode_step_ms", "prefill_chunk_ms", "sched_device_frac",
             "host_overhead_s", "window_compiles")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copy(os.path.join(here, "arch", TINY["arch"] + ".py"),
                bench_dir / "arch")
    for n in names:
        shutil.copy(os.path.join(here, "metrics", n + ".py"),
                    bench_dir / "metrics" / (n + ".py"))
    # rehearsal runs only program counters: list all five as such here
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 10,
        "configs": [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "bench/configs/tiny.json", "why": "test"}],
        "workloads": [{"name": "tiny.burst", "config": "tiny",
                       "traffic": "burst", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": n, "unit": "x", "better": "lower",
                       "source": "program_counter", "layer": "scheduler",
                       "moves": "setup_s"} for n in names]}))
    assert run.main(["--workload", "tiny.burst", "--seed", "3",
                     "--seconds", "0.1", "--rehearse"],
                    root=str(tmp_path)) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = out["metrics"]
    assert set(got) == {"host_overhead_s", "window_compiles"}
    assert got["window_compiles"]["value"] == 0
    assert 0 < got["host_overhead_s"]["value"] < 5
