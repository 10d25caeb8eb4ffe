"""The trainer's own host spans, counters and named scopes
(`repro.telemetry`): a tiny CoCoDC trainer traced by `jax.profiler` and
read back with `ProfileData`, and the segment program's HLO metadata."""
import glob
import re
import shutil
import tempfile

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.configs import CoCoDCConfig, get_config
from repro.core.trainer import (CrossRegionTrainer, SegmentRunner,
                                TrainerConfig, make_single_step)
from repro.models import api
from repro.optim import adamw_init

# span -> the span that caused it (None: opened by the caller's loop)
PARENT = {"plan": None, "segment": None, "data": "segment", "lr": "segment",
          "dispatch": "segment", "protocol": "segment",
          "sync.loss": "segment", "protocol.deliver": "protocol",
          "protocol.select": "protocol", "protocol.initiate": "protocol",
          "sync.adaptive_rate": "protocol.select"}
IDS = {"plan": {"t0"}, "segment": {"t0", "n"}, "data": {"t0", "n"},
       "dispatch": {"chunks"}, "protocol": {"t"},
       "protocol.deliver": {"frag", "t_init"}, "protocol.initiate": {"frag"}}


def _tiny_trainer():
    ccfg = CoCoDCConfig(num_workers=2, num_fragments=2, local_steps=8,
                        overlap_depth=2)
    tcfg = TrainerConfig(method="cocodc", local_batch=2, seq_len=16,
                         total_steps=200, warmup_steps=2, seed=1)
    return CrossRegionTrainer(get_config("bench_tiny"), ccfg, tcfg)


def _drive(tr, target):
    """The trainer's segment loop without eval or checkpoints."""
    while tr.step < target:
        t0 = tr.step
        end = tr._segment_end(t0, target, 0, 0)
        tr._run_segment(t0, end - t0 + 1)


def _traced(fn):
    from jax.profiler import ProfileData
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        return [(ev.name[len(telemetry.PREFIX):], ev.start_ns, ev.end_ns,
                 dict(ev.stats))
                for plane in ProfileData.from_file(path).planes
                for line in plane.lines for ev in line.events
                if ev.name.startswith(telemetry.PREFIX)]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _parent(spans, i):
    """Name of the innermost span enclosing spans[i], or None."""
    _, s, e, _ = spans[i]
    best = None
    for j, (name, s2, e2, _) in enumerate(spans):
        if j != i and s2 <= s and e <= e2 and (best is None
                                               or s2 >= spans[best][1]):
            best = j
    return None if best is None else spans[best][0]


def test_spans_nest_by_cause_and_counters_match_them():
    tr = _tiny_trainer()
    _drive(tr, 12)                        # compiles every program it needs
    before = tr.telemetry.snapshot()
    spans = _traced(lambda: _drive(tr, 40))
    diff = telemetry.difference(tr.telemetry.snapshot(), before)

    assert {s[0] for s in spans} == set(PARENT)
    for i, (name, _, _, stats) in enumerate(spans):
        assert _parent(spans, i) == PARENT[name], name
        assert set(stats) == IDS.get(name, set()), name
    # the spans of one segment carry its t0
    segs = sorted((s for s in spans if s[0] == "segment"),
                  key=lambda s: s[1])
    for _, s, e, st in segs:
        inner = [x for x in spans if s <= x[1] and x[2] <= e]
        assert [x[3]["t0"] for x in inner if x[0] == "data"] == [st["t0"]]
        assert [x[3]["n"] for x in inner if x[0] == "data"] == [st["n"]]
    assert [st["t0"] for *_, st in segs] == \
        sorted(x[3]["t0"] for x in spans if x[0] == "plan")
    lengths = [st["n"] for *_, st in segs]
    count = {k: sum(1 for s in spans if s[0] == k) for k in PARENT}

    assert diff["segments"] == len(segs) == count["sync.loss"]
    assert diff["steps"] == sum(lengths) == 28
    assert diff["scan_dispatches"] == sum(bin(n).count("1")
                                          for n in lengths)
    assert diff["initiations"] == count["protocol.initiate"] > 0
    assert diff["deliveries"] == count["protocol.deliver"] > 0
    assert diff["host_syncs"] == {"loss": diff["segments"],
                                  "adaptive_rate": diff["initiations"]}
    assert sum(diff["host_syncs"].values()) == \
        diff["segments"] + diff["initiations"]
    assert diff["boundary_s"] > 0
    assert tr.telemetry.segments == before["segments"] + len(segs)


def test_counters_stay_out_of_history_and_checkpoints():
    tr = _tiny_trainer()
    tr.run(steps=8, eval_every=4)
    assert tr.telemetry.host_syncs["eval"] == 2 * 3     # 2 nll + 1 ppl each
    ckpt = tr.checkpoint_state()
    flat = repr(ckpt["meta"]) + repr(ckpt["history"]) + repr(
        ckpt["scheduler"])
    for key in ("boundary_s", "scan_dispatches", "host_syncs"):
        assert key not in flat
    assert "host syncs" in tr.telemetry.line()


def test_segment_program_names_its_layers_in_op_metadata():
    cfg = get_config("bench_tiny")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    stack = jax.tree.map(lambda a: jnp.stack([a, a]), params)
    opt = jax.vmap(adamw_init)(stack)
    batch = {k: jnp.zeros((2, 2, 2, 16), jnp.int32)
             for k in ("tokens", "labels")}
    runner = SegmentRunner(make_single_step(cfg, weight_decay=0.1))
    text = runner._fn.fn.lower(stack, opt, batch,
                               jnp.zeros((2,))).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)

    def under(scope, name):
        return re.search(rf"(^|[/(]){scope}($|[/)])", name) is not None

    for scope in ("embed", "attention", "mlp", "head", "adamw"):
        assert any(under(scope, n) for n in names), scope
    # the backward pass and the remat recompute keep the scope
    assert any(under("attention", n) and "transpose(" in n for n in names)
    assert any(under("attention", n) and "rematted_computation" in n
               for n in names)
    assert any(under("mlp", n) and "transpose(" in n for n in names)


@pytest.mark.parametrize("reason", ["loss", "adaptive_rate"])
def test_read_blocks_and_counts_by_reason(reason):
    c = telemetry.Counters()
    x = jnp.arange(3.0) * 2
    got = c.read(reason, {"a": x, "b": x + 1})
    assert got["a"].tolist() == [0.0, 2.0, 4.0]
    assert got["b"].tolist() == [1.0, 3.0, 5.0]
    snap = c.snapshot()
    c.read(reason, x)
    c.scan_dispatched()                   # closes the boundary interval
    c.scan_dispatched()                   # not a boundary: no read between
    diff = telemetry.difference(c.snapshot(), snap)
    assert diff["host_syncs"] == {reason: 1}
    assert diff["scan_dispatches"] == 2
    assert diff["boundary_s"] > 0 and c._last_read is None
