"""The yardstick's arithmetic: FLOPs per token, the trace reduction, and the
reference's fragments and protocol step."""
from __future__ import annotations

import numpy as np
import pytest

import chipbench_fixtures as fx  # noqa: F401  (puts the checkout on sys.path)

from benchmarks.chip import flops, reference, traces
from benchmarks.chip.registry import Registry


def test_train_flops_per_token_by_hand():
    reg = Registry()
    # paper_150m: 12 x (4 x 768^2 attention + 3 x 768 x 2048 MLP) + the
    # 768 x 32000 head; causal attention 6 x 1024 x 768 per layer
    paper = 12 * (4 * 768 * 768 + 3 * 768 * 2048) + 768 * 32000
    assert flops.matmul_params_per_token(reg.config("paper_150m")) == paper
    assert flops.train_flops_per_token(reg.config("paper_150m"), 1024) == \
        6 * paper + 12 * 6 * 1024 * 768 == 713_687_040


def _ev(plane, line, name, start, dur):
    return traces.Event(plane, line, name, float(start), float(dur))


def test_trace_reduction_on_a_small_trace():
    dev, host = "/device:TPU:0", "/host:CPU"
    ev = [
        _ev(dev, traces.OPS_LINE, "%while.7 = (s32[]) while(s32[] %a)", 0,
            20),
        _ev(dev, traces.OPS_LINE, "%fusion.1 = f32[4]{0} fusion(%p)", 0, 10),
        _ev(dev, traces.OPS_LINE, "flash_decode_gqa.3", 5, 15),
        _ev(dev, traces.OPS_LINE, "fusion.2", 30, 10),
        _ev(dev, traces.OPS_LINE, "fusion.9", 60, 10),        # after window
        _ev(dev, traces.MODULES_LINE, "jit_run_segment(3)", 0, 20),
        _ev(dev, traces.MODULES_LINE, "jit_deliver(1)", 30, 10),
        _ev(host, "python", "bench.window", 0, 50),
        _ev(host, "python", "bench.data", 18, 14),
        _ev(host, "python", "bench.protocol", 40, 10),
    ]
    red = traces.reduce_events(ev, traces.window_from_spans(ev, "window"),
                               kernels=("flash_decode",))
    assert red["chips"] == 1 and red["window_s"] == pytest.approx(50e-9)
    assert red["busy_s"] == pytest.approx(30e-9)      # [0,20) + [30,40)
    assert red["programs"] == {"run_segment": {"s": 20e-9, "n": 1},
                               "deliver": {"s": 10e-9, "n": 1}}
    assert red["kernels"]["flash_decode"] == {"s": 15e-9, "n": 1}
    gaps = dict(red["idle_gaps"])
    assert gaps["data"] == pytest.approx(10e-9)       # [20, 30)
    assert gaps["protocol"] == pytest.approx(10e-9)   # [40, 50)
    assert dict(red["device_ops"])["fusion"] == pytest.approx(20e-9)


def test_busy_time_is_averaged_over_chips():
    ev = [_ev("/device:TPU:0", traces.OPS_LINE, "a", 0, 100),
          _ev("/device:TPU:1", traces.OPS_LINE, "a", 0, 50)]
    red = traces.reduce_events(ev, (0.0, 100.0))
    assert red["chips"] == 2
    assert red["busy_s"] == pytest.approx(75e-9)


def test_reference_fragments_are_the_strided_ones():
    import jax
    params = jax.eval_shape(lambda: reference.init_params(
        fx.TINY, jax.random.PRNGKey(0)))
    plan = reference.fragments(params, 2)
    assert [list(r) for r in plan["['layers']['attn']['wq']"]] == [[0], [1]]
    # whole leaves, biggest first: embed and lm_head (same size, embed first
    # in key order) to fragments 0 and 1, then final_norm to the lighter 0
    assert plan["['embed']"] == [Ellipsis, None]
    assert plan["['lm_head']"] == [None, Ellipsis]
    assert plan["['final_norm']"] == [Ellipsis, None]


@pytest.mark.parametrize("K", [2, 4])
def test_reference_fragments_match_the_programs(K):
    """The program's own fragmenter splits the same way (a check on the
    yardstick's reading of the configuration, not part of a run)."""
    import jax
    from repro.core.fragments import Fragmenter
    params = jax.eval_shape(lambda: reference.init_params(
        fx.TINY, jax.random.PRNGKey(0)))
    plan = reference.fragments(params, K)
    frag = Fragmenter(params, K, [fx.TINY["num_hidden_layers"]])
    for key, owners in frag.owners().items():
        ref = plan["".join(f"['{p}']" for p in key.split("/"))]
        if isinstance(owners, int):
            assert ref == [Ellipsis if p == owners else None
                           for p in range(K)]
        else:
            assert [() if r is None else tuple(r) for r in ref] == \
                list(owners)


def test_algorithm_1_by_hand():
    """Two regions, one whole-leaf fragment: the outer Nesterov step on the
    mean pseudo-gradient, then each region's progress since its snapshot
    re-applied with the lambda correction (Eqs. 4, 7, 8)."""
    import jax.numpy as jnp
    theta0 = {"w": jnp.array([1.0, 2.0])}
    mix = {"num_fragments": 1, "outer_lr": 0.7, "outer_momentum": 0.9,
           "comp_lambda": 0.5, "local_steps": 100}
    proto = reference.CoCoDC(theta0, mix)
    snap = [{"['w']": jnp.array([1.1, 2.2])}, {"['w']": jnp.array([0.9, 2.4])}]
    proto.initiate(0, 3, snap)
    before = [np.array([1.3, 2.1]), np.array([1.0, 2.5])]
    now = [{"['w']": jnp.asarray(b)} for b in before]
    proto.deliver(0, 7, now)
    delta = np.array([0.0, 0.3])
    g_new = np.array([1.0, 2.0]) + 0.7 * (delta + 0.9 * delta)
    for b, n, s in zip(before, now, snap):
        s = np.asarray(s["['w']"])
        g = (b - s) / 4.0
        want = g_new + 4.0 * (g + 0.5 * g * g * (g_new - s) / 100.0)
        assert np.allclose(np.asarray(n["['w']"]), want, atol=1e-6)
    assert np.allclose(np.asarray(proto.theta["['w']"]), g_new, atol=1e-6)
