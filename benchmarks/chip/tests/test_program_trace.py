"""Reading the program's own spans, counters and named scopes: the
reduction on a small hand-made trace, the op_names a real trace carries,
the five readers, and one traced run on the CPU at tiny sizes."""
from __future__ import annotations

import pytest

import chipbench_fixtures as fx

from benchmarks.chip import program_trace as pt
from benchmarks.chip import traces
from benchmarks.chip.registry import Registry

DEV, HOST = "/device:TPU:0", "/host:CPU"
SEG = "jit(run_segment)/while/body/closed_call"
# the segment program's op_names, as its HLO proto in the trace holds them
OP_NAMES = {"77": {
    "while.7": f"{SEG}/while",
    "fusion.1": f"{SEG}/vmap(jvp())/while/body/closed_call/attention/dot",
    "fusion.2": f"{SEG}/vmap(transpose(jvp()))/while/body/closed_call"
                "/checkpoint/rematted_computation/attention/exp",
    "fusion.3": f"{SEG}/vmap(transpose(jvp()))/while/body/closed_call"
                "/checkpoint/mlp/dot_general",
    "fusion.4": f"{SEG}/vmap(adamw)/mul",
    "fusion.5": f"{SEG}/vmap(transpose(jvp(head)))/exp",
    "copy.6": f"{SEG}/while/body/add"}}


def _ev(plane, line, name, start, dur):
    return traces.Event(plane, line, name, float(start), float(dur))


def _op(name, start, dur):
    return _ev(DEV, traces.OPS_LINE, f"%{name} = f32[4]{{0}} op(%a)", start,
               dur)


def _trace():
    return [
        _ev(DEV, traces.MODULES_LINE, "jit_run_segment(77)", 0, 60),
        _ev(DEV, traces.MODULES_LINE, "jit_deliver(78)", 70, 10),
        _op("while.7", 0, 50),                              # container
        _op("fusion.1", 0, 10), _op("fusion.2", 10, 10),
        _op("fusion.3", 20, 10), _op("fusion.4", 30, 5),
        _op("fusion.5", 35, 5),
        _op("copy.6", 40, 10),                              # under no scope
        _op("fusion.8", 70, 10),                            # another program
        _op("fusion.4", 120, 5),                            # after the window
        _ev(HOST, "python", "bench.window", 0, 100),
        _ev(HOST, "python", "repro.segment", 0, 65),
        _ev(HOST, "python", "repro.sync.loss", 5, 60),
        _ev(HOST, "python", "repro.plan", 65, 3),
        _ev(HOST, "python", "repro.segment", 68, 32),
        _ev(HOST, "python", "repro.protocol", 80, 15),
        _ev(HOST, "python", "repro.protocol.deliver", 81, 6),
    ]


def test_program_reduction_on_a_small_trace():
    ev = _trace()
    red = pt.reduce_program(ev, traces.window_from_spans(ev, "window"),
                            OP_NAMES)
    s = red["scopes"]
    assert set(s) == {"attention", "mlp", "adamw", "head", "other"}
    assert s["attention"] == pytest.approx(
        {"s": 20e-9, "n": 2, "forward": 10e-9, "remat": 10e-9,
         "backward": 0.0})
    assert s["mlp"]["backward"] == pytest.approx(10e-9)
    assert s["adamw"] == pytest.approx({"s": 5e-9, "n": 1, "forward": 5e-9,
                                        "remat": 0.0, "backward": 0.0})
    assert s["head"]["backward"] == pytest.approx(5e-9)
    assert s["other"] == pytest.approx({"s": 10e-9, "n": 1, "forward": 10e-9,
                                        "remat": 0.0, "backward": 0.0})
    assert red["other_ops"] == [[f"{SEG}/while/body/add", pytest.approx(
        10e-9)]]
    # idle: [50, 70) and [80, 100); midpoints 60 (sync.loss) and 90
    # (protocol; the delivery closed at 87)
    gaps = dict(red["idle_gaps_program"])
    assert gaps == pytest.approx({"sync.loss": 20e-9, "protocol": 20e-9})
    # without the program's op_names every op of the segment is `other`
    blind = pt.reduce_program(ev, (0.0, 100.0), {})
    assert set(blind["scopes"]) == {"other"}
    assert blind["scopes"]["other"]["n"] == 6
    assert blind["other_ops"][0] == ["fusion.1", pytest.approx(10e-9)]


def test_idle_outside_every_program_span_is_host_other():
    ev = [_ev(HOST, "python", "bench.window", 0, 100),
          _op("fusion.1", 0, 40), _op("fusion.2", 80, 10),
          _ev(HOST, "python", "repro.segment", 0, 65)]
    red = pt.reduce_program(ev, (0.0, 100.0), OP_NAMES)
    # a gap goes whole to the span open at its midpoint: 60 and 95
    assert dict(red["idle_gaps_program"]) == pytest.approx(
        {"segment": 40e-9, "host.other": 10e-9})
    assert red["scopes"] == {}                  # no run_segment module


@pytest.mark.parametrize("op_name,scope,pass_", [
    (f"{SEG}/vmap(jvp(embed))/gather", "embed", "forward"),
    (f"{SEG}/vmap(transpose(jvp(embed)))/scatter-add", "embed", "backward"),
    (f"{SEG}/vmap(jvp())/while/body/closed_call/attention/jit(_where)",
     "attention", "forward"),
    (f"{SEG}/attention/le;attention/broadcast_in_dim;vmap(jvp(embed))",
     "attention", "forward"),
    (f"{SEG}/vmap(jvp())/while/body/closed_call/mlp/jit(silu)", "mlp",
     "forward"),
    (f"{SEG}/vmap(adamw)/sqrt", "adamw", "forward"),
    (f"{SEG}/vmap(jvp(head))/while/body/closed_call/jit(take_along_axis)",
     "head", "forward"),
    (f"{SEG}/while/body/add", "other", "forward"),
    ("", "other", "forward"),
])
def test_scope_and_pass_of_an_op_name(op_name, scope, pass_):
    assert pt.scope_of(op_name) == scope
    assert pt.pass_of(op_name) == pass_


def test_op_names_come_from_the_traces_own_hlo_protos():
    """A real trace (the CPU's) keeps each program's HLO proto; the named
    scope reaches the op_names read back from it, keyed by program."""
    import glob
    import shutil
    import tempfile
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x) @ x
        return y.sum()

    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        f(x).block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        names = pt.program_op_names(Path(path).read_bytes())
    finally:
        shutil.rmtree(d, ignore_errors=True)
    progs = [n for n in names.values()
             if any(v.startswith("jit(f)/") for v in n.values())]
    assert len(progs) == 1
    scoped = {k: v for k, v in progs[0].items() if "attention" in v}
    assert scoped and all(pt.scope_of(v) == "attention"
                          for v in scoped.values())
    assert pt.program_key("jit_run_segment(1084)") == "1084"
    assert pt.program_key("jit_f") == "jit_f"


READERS = {"attention_ms_per_step": 40.0, "mlp_ms_per_step": 20.0,
           "head_ms_per_step": 8.0, "adamw_ms_per_step": 12.0,
           "boundary_ms_per_segment": 15.0}


def test_the_five_readers_on_a_hand_made_run():
    reg = Registry()
    scopes = {k: {"s": v, "n": 1, "forward": v, "remat": 0.0,
                  "backward": 0.0}
              for k, v in {"attention": 0.4, "mlp": 0.2, "head": 0.08,
                           "adamw": 0.12, "other": 0.01}.items()}
    run = {"record": {"steps": 10, "telemetry": {
               "segments": 4, "boundary_s": 0.06, "steps": 10,
               "host_syncs": {"loss": 4, "adaptive_rate": 2}}},
           "trace": {"programs": {}, "scopes": scopes}}
    for name, want in READERS.items():
        assert reg.metric(name).read(run) == pytest.approx(want), name
    # a run of a program with no scopes or counters reads nothing
    bare = {"record": {"steps": 10}, "trace": {"programs": {}}}
    for name in READERS:
        assert reg.metric(name).read(bare) is None, name


def test_traced_run_reads_the_programs_counters(tmp_path):
    reg = fx.tiny_tree(tmp_path)
    out = pt.run_traced("tiny.train", 2 ** 31 + 777, 0.5,
                        require_chip=False, registry=reg)
    assert out["correct"], out["checks"]
    t = out["telemetry"]
    assert t["steps"] == out["attempted"] > 0
    assert t["segments"] > 0 and t["boundary_s"] > 0
    assert t["host_syncs"]["loss"] == t["segments"]
    assert sum(t["host_syncs"].values()) == t["segments"] + t["initiations"]
    assert out["metrics"]["boundary_ms_per_segment"] == pytest.approx(
        1e3 * t["boundary_s"] / t["segments"])
