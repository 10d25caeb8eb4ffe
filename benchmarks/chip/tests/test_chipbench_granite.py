"""Granite 3.0's MoE decoder against its plain reference
(`references/granite_moe.py`) at tiny sizes on the CPU: a share of the
experts is the uncut layer's part, the program's loss and gradients are the
reference's with every muP scalar, a whole run of a Granite-shaped cell
comes out correct with the FLOP count by hand, and the two MoE readers."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_fixtures as fx

from benchmarks.chip import run, traces, train_cell
from benchmarks.chip.registry import Registry

# 2 layers of width 64, 4 query and 2 KV heads of 16, 8 experts of 32,
# top-2, this chip shard 1 of 4 (experts 2 and 3), Granite's scalars
TINY_GRANITE = {
    **fx.TINY, "name": "tiny_granite", "arch": "granite_moe_3b_a800m",
    "reference": "granite_moe",
    "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 32, "vocab": 512,
                  "moe": {"num_experts": 8, "top_k": 2, "expert_shards": 4,
                          "expert_shard": 1}},
    "intermediate_size": 32, "num_local_experts": 2, "expert_shards": 4,
    "expert_shard": 1, "num_experts_per_tok": 2, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "embedding_multiplier": 12.0,
    "attention_multiplier": 0.015625, "residual_multiplier": 0.22,
    "logits_scaling": 6.0, "router_aux_loss_coef": 0.01}
IDENTITY = {"embedding_multiplier": 1.0, "attention_multiplier": 0.25,
            "residual_multiplier": 1.0, "logits_scaling": 1.0}


MOE_READERS = ("expert_matmul_roofline", "moe_dispatch_ms_per_step")


def _granite():
    return Registry().reference("granite_moe")


def _program(cfg):
    """The program's ModelConfig for a file, at float32 compute."""
    mcfg = train_cell.model_config(cfg, _granite())
    return dataclasses.replace(mcfg, compute_dtype="float32")


def _with(cfg, **scalars):
    over = {**cfg["overrides"], **scalars}
    return {**cfg, **scalars, "overrides": over}


def _batch(cfg, B=2, S=16):
    k = jax.random.PRNGKey(11)
    v = cfg["vocab_size"]
    return (jax.random.randint(k, (B, S), 0, v),
            jax.random.randint(jax.random.fold_in(k, 1), (B, S), 0, v))


def _loss_and_grads(cfg):
    """(program, reference) loss and gradient leaves on the same seeded
    weights, which each side draws from the same key."""
    from repro.models import api
    model, mcfg = _granite(), _program(cfg)
    key = jax.random.PRNGKey(5)
    params = api.init_params(mcfg, key)
    ref_params = model.init_params(cfg, key)
    jax.tree.map(np.testing.assert_array_equal, params, ref_params)
    tokens, labels = _batch(cfg)
    lp, gp = jax.value_and_grad(lambda p: api.loss_fn(
        mcfg, p, {"tokens": tokens, "labels": labels})[0])(params)
    lr, gr = jax.value_and_grad(lambda p: model.loss(
        cfg, p, tokens, labels))(params)
    return (lp, gp), (lr, gr)


def _assert_close(prog, ref):
    (lp, gp), (lr, gr) = prog, ref
    np.testing.assert_allclose(float(lp), float(lr), rtol=0, atol=1e-5)
    flat_r = dict(jax.tree_util.tree_flatten_with_path(gr)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(gp)[0]:
        want = np.asarray(flat_r[path])
        scale = max(float(np.abs(want).max()), 1e-12)
        np.testing.assert_allclose(np.asarray(g) / scale, want / scale,
                                   rtol=0, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))


def test_shares_add_up_to_the_uncut_layer():
    """At E=8, K=2, the 4 shares of 2 experts each route over all 8 and
    compute their own experts' part: the parts add up to the uncut
    layer's output, the program's and the reference's alike."""
    from repro.configs.base import MoEConfig
    from repro.models import moe as moe_lib
    D, F, key = 32, 64, jax.random.PRNGKey(1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, D))

    def layer(moe):
        return jax.tree.map(lambda a: a[0], moe_lib.init_moe_params(
            key, 1, D, F, moe, jnp.float32))

    whole = MoEConfig(num_experts=8, top_k=2)
    uncut = moe_lib.moe_ffn(x, layer(whole), whole).y
    parts = 0.0
    for s in range(4):
        share = MoEConfig(num_experts=8, top_k=2, expert_shards=4,
                          expert_shard=s)
        lp = layer(share)
        np.testing.assert_array_equal(lp["w_up"],
                                      layer(whole)["w_up"][2 * s:2 * s + 2])
        parts = parts + moe_lib.moe_ffn(x, lp, share).y
    np.testing.assert_allclose(parts, uncut, rtol=0, atol=1e-5)
    m = {"E": 8, "E_held": 8, "first": 0, "top_k": 2}
    ref, _ = _granite().moe(x.reshape(-1, D), layer(whole), m)
    np.testing.assert_allclose(parts.reshape(-1, D), ref, rtol=0, atol=1e-5)


def test_program_matches_reference_with_every_scalar():
    """The program's loss_fn against the reference on seeded random weights
    at a tiny Granite shape (shard 1 of 4), with all four scalars: the loss
    (NLL and balance term) and every gradient leaf."""
    _assert_close(*_loss_and_grads(TINY_GRANITE))


@pytest.mark.parametrize("scalar", sorted(IDENTITY))
def test_each_scalar_alone_matches_reference(scalar):
    """Each scalar set alone, the others at the identity: program against
    reference, and the scalar changes the loss (it is applied)."""
    plain = _with(TINY_GRANITE, **IDENTITY)
    one = _with(plain, **{scalar: TINY_GRANITE[scalar]})
    prog, ref = _loss_and_grads(one)
    _assert_close(prog, ref)
    assert abs(float(prog[0]) - float(_loss_and_grads(plain)[0][0])) > 1e-4


@pytest.fixture(scope="module")
def granite_tree(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("granite")
    reg = fx.tiny_tree(tmp)
    (tmp / "configs" / "tiny_granite.json").write_text(
        json.dumps(TINY_GRANITE))
    (tmp / "limits" / "tiny_granite.train.json").write_text(
        json.dumps(fx.TRAIN_LIMITS))
    bench = reg.benchmark()
    bench["workloads"].append({"name": "tiny_granite.train",
                               "config": "tiny_granite",
                               "traffic": "train.tiny", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("tiny_granite.train")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return reg


def test_a_granite_shaped_run_is_correct(granite_tree):
    """A whole run through the trainer's segment loop at bf16 against the
    f32 reference comes out correct, and its FLOPs per token are the hand
    count: per layer q and o 64 x 64, k and v 64 x 32, the router 64 x 8
    and top_k x held / E = 2 x 2 / 8 of an expert's three 64 x 32
    matrices; the tied head 64 x 512 once; causal attention at S/2."""
    seen = {}
    out = run.run_cell("tiny_granite.train", 2 ** 31 + 161, 0.5, True,
                       registry=granite_tree, require_chip=False,
                       extra_metrics=MOE_READERS, details=seen)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # no kernel runs on the CPU: the MoE readers read nothing
    assert not set(MOE_READERS) & set(out["metrics"])
    assert seen["record"]["telemetry"]["expert_matmul_kernel_layers"] == 0
    layer = 2 * 64 * 64 + 2 * 64 * 32 + 64 * 8 + 0.5 * 3 * 64 * 32
    per_token = 2 * layer + 64 * 512
    assert seen["record"]["flops_per_token"] == \
        6 * per_token + 2 * 6 * 16 * 4 * 16


def test_expert_readers_on_a_hand_made_run():
    reg = Registry()
    cfg = reg.config("granite_3b_a800m")
    mix = reg.traffic("train.cocodc_paper")
    dims = _granite().dims(cfg)
    roof = reg.metric("expert_matmul_roofline")
    disp = reg.metric("moe_dispatch_ms_per_step")
    # 4 regions x 2 x 1024 tokens x 8 choices x 10 / 40 held = 16384
    # pairs a call on average, 2 x 16384 x 1536 x 512 FLOPs
    assert roof.pairs_per_call(mix, dims) == pytest.approx(16384)
    # 2 steps of 4 layers: 72 expert_gmm calls of 0.3 ms, 24 expert_tgmm
    # calls of 0.4 ms, one trace event each, tallied as the run tallies
    # them, with both readers' KERNELS joined
    op = lambda name, t: traces.Event("/device:TPU:0", traces.OPS_LINE,
                                      f"%{name} = bf16[8]{{0}} custom-call()",
                                      t, 1e3 * (300 if "_gmm" in name else 400))
    events = [op(f"expert_gmm.{i % 9}", 1e6 * i) for i in range(72)] + \
        [op(f"expert_tgmm.{i % 3}", 1e6 * (100 + i)) for i in range(24)]
    kernels = traces.reduce_events(events, (0, 1e9),
                                   roof.KERNELS + disp.KERNELS)["kernels"]
    assert kernels["expert_gmm"]["n"] == 72 and kernels["expert_"]["n"] == 96
    view = {"trace": {"kernels": kernels, "scopes": {"mlp": {"s": 0.05}}},
            "record": {"steps": 2}, "mix": mix, "dims": dims,
            "peaks": {"bf16_flops_per_s": 197e12}}
    assert roof.read(view) == pytest.approx(
        100 * 96 * 2 * 16384 * 1536 * 512 / (31.2e-3 * 197e12))
    assert disp.read(view) == pytest.approx(1e3 * (0.05 - 31.2e-3) / 2)
    # no call of the kernels (a dense model, the oracle, no chip): nothing
    for k in ({}, {"expert_gmm": {"s": 0.0, "n": 0},
                   "expert_": {"s": 0.0, "n": 0}}):
        quiet = {**view, "trace": {**view["trace"], "kernels": k}}
        assert roof.read(quiet) is None and disp.read(quiet) is None
    dense = {**view, "dims": {"L": 12, "H": 12, "hd": 64}}
    assert roof.read(dense) is None
