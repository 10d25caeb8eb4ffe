"""A configuration that brings its own reference model, FLOP count and
readers as new files; the program's agreement with it as data; the counts
that follow the configuration; and the program's scopes and counters in
every traced run."""
from __future__ import annotations

import json
import textwrap

import numpy as np
import pytest

import chipbench_fixtures as fx

from benchmarks.chip import flops, reference, run, train_cell
from benchmarks.chip.registry import HERE, Registry

SEVEN = ("attention_ms_per_step", "mlp_ms_per_step", "head_ms_per_step",
         "adamw_ms_per_step", "boundary_ms_per_segment",
         "flash_attention_fwd_roofline", "flash_attention_bwd_roofline")

# the dense model under another name, with one more field the program has
# to agree on and a FLOP count of its own
OWN_MODEL = textwrap.dedent('''
    from benchmarks.chip.reference import dims, init_params, loss
    from benchmarks.chip import reference


    def program_fields(cfg):
        return {**reference.program_fields(cfg), "qk_norm": False}


    def matmul_params_per_token(cfg):
        return reference.matmul_params_per_token(cfg) + 1000
''')
# a reader of a counter no listed metric reads
OWN_READER = textwrap.dedent('''
    def read(run):
        t = run["record"].get("telemetry")
        if not t or not t.get("segments"):
            return None
        return t["scan_dispatches"] / t["segments"]
''')


def _files():
    return {p: p.read_bytes() for p in HERE.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def _own_model_tree(tmp, model_src=OWN_MODEL):
    reg = fx.tiny_tree(tmp)
    (tmp / "references").mkdir()
    (tmp / "references" / "own_dense.py").write_text(model_src)
    (tmp / "metrics" / "scan_dispatches_per_segment.py").parent.mkdir(
        exist_ok=True)
    (tmp / "metrics" / "scan_dispatches_per_segment.py").write_text(
        OWN_READER)
    (tmp / "configs" / "own.json").write_text(json.dumps(
        {**fx.TINY, "name": "own", "reference": "own_dense"}))
    (tmp / "limits" / "own.train.json").write_text(
        json.dumps(fx.TRAIN_LIMITS))
    bench = reg.benchmark()
    bench["workloads"].append({"name": "own.train", "config": "own",
                               "traffic": "train.tiny", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][0]["workloads"].append("own.train")
    bench["per_layer"].append(
        {"name": "scan_dispatches_per_segment", "unit": "1",
         "better": "lower", "source": "program_counter",
         "layer": "trainer host loop", "moves": "train_tokens_per_s",
         "workloads": ["own.train"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return reg


def test_a_later_model_brings_its_own_files_and_edits_none(tmp_path):
    before = _files()
    reg = _own_model_tree(tmp_path)
    seen = {}
    out = run.run_cell("own.train", 2 ** 31 + 4243, 0.5, True, registry=reg,
                       require_chip=False, details=seen)
    assert out["correct"], out["checks"]
    # its FLOP count, its reader of a counter, and the program's counters
    model = reg.model(reg.config("own"))
    assert model.__name__ != reference.__name__
    assert seen["record"]["flops_per_token"] == \
        flops.train_flops_per_token(fx.TINY, 16) + 6 * 1000
    t = seen["record"]["telemetry"]
    assert out["metrics"]["scan_dispatches_per_segment"]["value"] == \
        t["scan_dispatches"] / t["segments"] >= 1
    assert _files() == before


def test_the_configurations_own_loss_is_the_yardstick(tmp_path):
    """A model module whose loss is not the program's makes the run not
    correct: the comparison runs the module the configuration names."""
    wrong = OWN_MODEL.replace(
        "from benchmarks.chip.reference import dims, init_params, loss",
        "from benchmarks.chip.reference import dims, init_params\n"
        "from benchmarks.chip.reference import loss as _loss\n\n\n"
        "def loss(cfg, params, tokens, labels, lowp=None):\n"
        "    return 1.01 * _loss(cfg, params, tokens, labels, lowp)")
    reg = _own_model_tree(tmp_path, wrong)
    out = run.run_cell("own.train", 2 ** 31 + 4243, 0.5, False, registry=reg,
                       require_chip=False)
    assert not out["correct"]
    assert out["checks"]["loss_gap"]["value"] > 0.05


def test_an_unknown_reference_names_the_roots(tmp_path):
    reg = Registry(roots=[tmp_path, HERE])
    with pytest.raises(KeyError) as e:
        reg.model({**fx.TINY, "reference": "no_such_model"})
    assert "references/no_such_model.py" in str(e.value)
    assert str(tmp_path) in str(e.value) and str(HERE) in str(e.value)
    assert Registry().model(fx.TINY) is reference


class _Fields:
    def __init__(self, fields):
        self.fields = fields

    def program_fields(self, cfg):
        return self.fields


@pytest.mark.parametrize("fields,named", [
    ({"n_layers": 3}, "n_layers"),
    ({"moe.num_experts": 10}, "moe.num_experts"),     # a dense program
    ({"no_such_field": 1}, "no_such_field"),
])
def test_program_fields_disagreement_names_the_field(fields, named):
    with pytest.raises(ValueError, match=named.replace(".", r"\.")):
        train_cell.model_config(fx.TINY, _Fields(fields))


def test_dense_file_refuses_a_program_with_experts():
    moe = {**fx.TINY, "arch": "granite_moe_3b_a800m",
           "overrides": {**fx.TINY["overrides"], "tie_embeddings": False,
                         "moe": {"num_experts": 10}}}
    with pytest.raises(ValueError, match="moe"):
        train_cell.model_config(moe)


def test_nested_overrides_replace_fields_of_a_dataclass():
    cfg = {**fx.TINY, "arch": "granite_moe_3b_a800m",
           "overrides": {**fx.TINY["overrides"], "tie_embeddings": False,
                         "moe": {"num_experts": 10}}}
    fields = {"moe.num_experts": 10, "moe.top_k": 8, "n_layers": 2,
              "vocab": 512}
    mcfg = train_cell.model_config(cfg, _Fields(fields))
    assert (mcfg.moe.num_experts, mcfg.moe.top_k) == (10, 8)
    with pytest.raises(ValueError, match="rope_theta"):
        train_cell.model_config(
            {**fx.TINY, "overrides": {"rope_theta": {"x": 1}}})


# Recorded with the harness of the parent commit, before the reference took
# its model as a module: the reference alone on fixed batches and protocol
# events, and a whole run's compared numbers.
PARENT_LOSSES = [
    [6.86903715133667, 6.914996147155762, 6.507638931274414,
     6.975912094116211, 6.842458724975586, 6.577444076538086],
    [6.855597972869873, 6.9121904373168945, 7.005215167999268,
     6.886136054992676, 6.84788179397583, 6.665624618530273]]
PARENT_SUMS = {"grad": 3.497446217574179, "change3": 0.0013800979004372493,
               "change6": 0.005021080874939798}
PARENT_CHECKS = {"loss_gap": 0.004009246826171875,
                 "grad_gap": 0.0038310387982053,
                 "delta_gap": 0.0007087990186123831,
                 "sync_gap": 0.0004342367462035174}


def test_reference_readings_are_the_parents(tmp_path):
    import jax
    reg = fx.tiny_tree(tmp_path)
    mix = reg.traffic("train.tiny")
    rng = np.random.default_rng(5)
    batches = [[tuple(rng.integers(0, 512, (2, 16)).astype(np.int32)
                      for _ in range(2)) for _ in range(6)]
               for _ in range(2)]
    p0 = jax.jit(lambda k: reference.init_params(fx.TINY, k))(
        jax.random.PRNGKey(7))
    ref = reference.train(reference, fx.TINY, p0, batches, mix,
                          [(0, 1, 3), (1, 2, 5)], (3, 6))
    assert ref["losses"].tolist() == PARENT_LOSSES
    assert float(sum(v.sum() for v in ref["grad"].values())) == \
        PARENT_SUMS["grad"]
    for n in (3, 6):
        assert float(sum(v.sum() for v in ref["change"][n].values())) == \
            PARENT_SUMS[f"change{n}"]
    out = run.run_cell("tiny.train", 2 ** 31 + 4242, 0.5, False,
                       registry=reg, require_chip=False)
    assert {k: c["value"] for k, c in out["checks"].items()} == PARENT_CHECKS


MOE_MODEL = textwrap.dedent('''
    def dims(cfg):
        return {"L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
                "H": cfg["num_attention_heads"],
                "KV": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
                "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
                "E": cfg["num_local_experts"],
                "top_k": cfg["num_experts_per_tok"]}


    def matmul_params_per_token(cfg):
        m = dims(cfg)
        D, hd = m["D"], m["hd"]
        attn = 2 * D * m["H"] * hd + 2 * D * m["KV"] * hd
        moe = D * m["E"] + m["top_k"] * 3 * D * m["F"]
        return m["L"] * (attn + moe) + D * m["V"]
''')
GRANITE_CUT = {"num_hidden_layers": 4, "hidden_size": 1536,
               "num_attention_heads": 24, "num_key_value_heads": 8,
               "head_dim": 64, "intermediate_size": 512,
               "num_local_experts": 40, "num_experts_per_tok": 8,
               "vocab_size": 12288, "reference": "moe_shape"}


@pytest.mark.parametrize("which", ["paper_150m", "moe", "attention"])
def test_flop_counts_by_hand(tmp_path, which):
    if which == "paper_150m":
        cfg = Registry().config("paper_150m")
        paper = 12 * (4 * 768 * 768 + 3 * 768 * 2048) + 768 * 32000
        assert flops.matmul_params_per_token(cfg, reference) == paper
        assert flops.train_flops_per_token(cfg, 1024) == 713_687_040
    elif which == "moe":
        (tmp_path / "references").mkdir()
        (tmp_path / "references" / "moe_shape.py").write_text(MOE_MODEL)
        model = Registry(roots=[tmp_path, HERE]).model(GRANITE_CUT)
        # per layer: q and o 1536 x 1536, k and v 1536 x 512, the router
        # 1536 x 40, and 8 of the 40 experts' three 1536 x 512 matrices;
        # the tied head 1536 x 12288
        layer = 2 * 1536 * 1536 + 2 * 1536 * 512 + 1536 * 40 + \
            8 * 3 * 1536 * 512
        want = 4 * layer + 1536 * 12288
        assert flops.matmul_params_per_token(GRANITE_CUT, model) == want
        assert flops.train_flops_per_token(GRANITE_CUT, 1024, model) == \
            6 * want + 4 * 6 * 1024 * 24 * 64
    else:
        # paper_150m's kernels: B = 4 regions x 2, 12 heads, S 1024, hd 64
        assert flops.attention_fwd_flops(8, 12, 1024, 64) == 12_884_901_888
        assert flops.attention_bwd_flops(8, 12, 1024, 64) == 32_212_254_720
        # GQA changes nothing: the count is of query heads
        assert flops.attention_fwd_flops(8, 24, 1024, 64) == \
            2 * 12_884_901_888


def test_roofline_readers_on_a_hand_made_run():
    reg = Registry()
    mix = reg.traffic("train.cocodc_paper")
    dims = reference.dims(reg.config("paper_150m"))
    peaks = {"bf16_flops_per_s": 197e12}
    kernels = {"flash_attention_fwd": {"s": 24 * 0.378e-3, "n": 24},
               "flash_attention_bwd": {"s": 12 * 0.606e-3, "n": 12}}
    view = {"trace": {"kernels": kernels}, "mix": mix, "dims": dims,
            "peaks": peaks}
    fwd = reg.metric("flash_attention_fwd_roofline")
    bwd = reg.metric("flash_attention_bwd_roofline")
    assert fwd.KERNELS == ("flash_attention_fwd",)
    assert bwd.KERNELS == ("flash_attention_bwd",)
    assert fwd.read(view) == pytest.approx(
        100 * 12_884_901_888 / (0.378e-3 * 197e12))     # 17.3%
    assert bwd.read(view) == pytest.approx(
        100 * 32_212_254_720 / (0.606e-3 * 197e12))     # 27.0%
    # no call of the kernel, or no peak: nothing to read, never 0
    for k in ({}, {"flash_attention_fwd": {"s": 0.0, "n": 0}}):
        assert fwd.read({**view, "trace": {"kernels": k}}) is None
    assert bwd.read({**view, "peaks": None}) is None


def test_a_traced_cpu_run_carries_scopes_and_telemetry(tmp_path):
    """Every traced run reduces the program's scopes and idle gaps by
    program span and records the counters' difference. On the CPU no op
    runs on a TPU plane and no kernel is called: the scope and roofline
    readers read nothing, the counter reader a number."""
    reg = fx.tiny_tree(tmp_path)
    seen = {}
    out = run.run_cell("tiny.train", 2 ** 31 + 901, 0.5, True, registry=reg,
                       require_chip=False, extra_metrics=SEVEN,
                       details=seen)
    assert out["correct"], out["checks"]
    red, rec = seen["trace"], seen["record"]
    assert {"scopes", "other_ops", "idle_gaps_program"} <= set(red)
    assert rec["telemetry"]["steps"] == rec["steps"] == out["attempted"]
    assert out["breakdown"]["idle_gaps"] == red["idle_gaps_program"][:10]
    got = {n: reg.metric(n).read(seen) for n in SEVEN}
    assert got.pop("boundary_ms_per_segment") == pytest.approx(
        1e3 * rec["telemetry"]["boundary_s"] / rec["telemetry"]["segments"])
    assert got == {n: None for n in got}
