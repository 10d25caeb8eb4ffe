"""Delta wire codec: oracle/kernel bitwise parity, round-trip error bounds,
error-feedback bias cancellation, engine threading (bytes accounting, residual
state), checkpoint upgrade paths, and the bitwise codec="none" pin against the
pre-codec (PR 5) trajectories."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import CoCoDCConfig
from repro.core.trainer import CrossRegionTrainer, TrainerConfig
from repro.kernels.delta_codec import ops as codec_ops
from repro.kernels.delta_codec import ref as ref_lib
from repro.kernels.delta_codec.ops import CODEC_BITS, wire_bytes

KEY = jax.random.PRNGKey(0)

CODECS = ("int8", "int4")
SHAPES = ((7,), (300,), (33, 65), (2048,), (5, 1000))


def rand(shape, seed=0, scale=1.0):
    return jax.random.normal(jax.random.fold_in(KEY, seed), shape,
                             jnp.float32) * scale


def _block_scales(x, block, levels):
    """Per-block absmax/levels over the padded flat layout — the max per-
    element reconstruction half-step."""
    flat = np.asarray(x, np.float32).reshape(-1)
    pad = (-len(flat)) % block
    flat = np.pad(flat, (0, pad))
    absmax = np.abs(flat.reshape(-1, block)).max(axis=1)
    return absmax * np.float32(1.0 / levels)


# ---------------------------------------------------------------------------
# oracle <-> kernel bitwise parity, wire format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("block", [256, 512])
@pytest.mark.parametrize("shape", SHAPES)
def test_ref_pallas_bitwise_parity(codec, block, shape):
    """The fused kernel (interpret mode on CPU) and the pure-jnp oracle agree
    BITWISE on packed codes, scales, and the round-tripped payload."""
    x = rand(shape, seed=hash((codec, block, shape)) % 1000)
    pr, sr = codec_ops.encode_array(x, codec=codec, block=block, impl="ref")
    pk, sk = codec_ops.encode_array(x, codec=codec, block=block, impl="pallas")
    np.testing.assert_array_equal(np.asarray(pr), np.asarray(pk))
    np.testing.assert_array_equal(np.asarray(sr), np.asarray(sk))
    rt_r = codec_ops.codec_roundtrip_array(x, codec=codec, block=block,
                                           impl="ref")
    rt_k = codec_ops.codec_roundtrip_array(x, codec=codec, block=block,
                                           impl="pallas")
    np.testing.assert_array_equal(np.asarray(rt_r), np.asarray(rt_k))


def test_pallas_rejects_unaligned_block(monkeypatch):
    x = rand((128,))
    with pytest.raises(ValueError, match="block"):
        codec_ops.encode_array(x, codec="int8", block=10, impl="pallas")
    # on the CPU auto is the oracle, which takes any block
    codec_ops.codec_roundtrip_array(x, codec="int8", block=10, impl="auto")
    # off the CPU auto means the kernel: an unaligned block raises instead
    # of quietly running the oracle on the device
    monkeypatch.setattr(codec_ops, "is_cpu", lambda: False)
    with pytest.raises(ValueError, match="block"):
        codec_ops.encode_array(x, codec="int8", block=10, impl="auto")


def test_int4_pack_unpack_exact():
    """Halves-packing is lossless on the code ints, including negatives."""
    codes = jnp.arange(-7, 8, dtype=jnp.int8)
    codes = jnp.tile(codes, 36)[: 512].reshape(2, 256)
    packed = ref_lib.pack_ref(codes, bits=4)
    assert packed.shape == (2, 128)
    np.testing.assert_array_equal(np.asarray(ref_lib.unpack_ref(packed, bits=4)),
                                  np.asarray(codes))


@pytest.mark.parametrize("codec", CODECS)
def test_roundtrip_error_bounded_by_half_step(codec):
    """Per element, |x - decode(encode(x))| <= block_absmax/levels/2: absmax
    scaling never clips, so the only loss is rounding to the nearest level."""
    levels = {"int8": 127, "int4": 7}[codec]
    for seed, shape in enumerate(SHAPES):
        x = rand(shape, seed=seed, scale=3.0)
        rt = codec_ops.codec_roundtrip_array(x, codec=codec, block=256)
        err = np.abs(np.asarray(x) - np.asarray(rt)).reshape(-1)
        half = np.repeat(_block_scales(x, 256, levels) * 0.5, 256)[: err.size]
        assert (err <= half + 1e-7).all()


def test_zero_block_roundtrips_to_exact_zero():
    x = jnp.zeros((512,), jnp.float32)
    packed, scales = codec_ops.encode_array(x, codec="int8", block=256)
    assert not np.asarray(packed).any() and not np.asarray(scales).any()
    rt = codec_ops.codec_roundtrip_array(x, codec="int8", block=256)
    assert not np.asarray(rt).any()


def test_wire_bytes_formula_and_ratios():
    """codes + one f32 scale per block; int8/int4 at block=256 clear the
    3.5x / 7x compression floors that the sweep frontier enforces."""
    assert wire_bytes(256, codec="int8", block=256) == 256 + 4
    assert wire_bytes(256, codec="int4", block=256) == 128 + 4
    assert wire_bytes(257, codec="int8", block=256) == 257 + 8
    assert wire_bytes(1, codec="int4", block=256) == 1 + 4
    n = 1 << 20
    assert n * 4 / wire_bytes(n, codec="int8", block=256) > 3.5
    assert n * 4 / wire_bytes(n, codec="int4", block=256) > 7.0


# ---------------------------------------------------------------------------
# error feedback: cumulative quantization bias -> ~0
# ---------------------------------------------------------------------------


def _ef_bias(d, rounds, codec, ef):
    """Mean cumulative bias per round of repeatedly shipping the SAME delta
    through the codec, with/without the EF residual fold-in."""
    e = jnp.zeros_like(d)
    acc = jnp.zeros_like(d)
    for _ in range(rounds):
        din = d + e if ef else d
        q = codec_ops.codec_roundtrip_array(din, codec=codec, block=256)
        if ef:
            e = din - q
        acc = acc + (q - d)
    return float(jnp.abs(acc).mean()) / rounds


@pytest.mark.parametrize("codec", CODECS)
def test_error_feedback_cancels_cumulative_bias(codec):
    """Without EF the per-round rounding bias accumulates linearly; with EF
    the residual re-enters the next round and the time-averaged payload
    converges to the true delta (EF-SGD)."""
    d = rand((4096,), seed=7, scale=0.05)
    with_ef = _ef_bias(d, 24, codec, ef=True)
    without = _ef_bias(d, 24, codec, ef=False)
    levels = {"int8": 127, "int4": 7}[codec]
    step = float(_block_scales(d, 256, levels).mean())
    # EF: bounded by ~one quantization step spread over the window
    assert with_ef < 2.0 * step / 24 + 1e-9
    # and at least an order of magnitude below the open-loop bias (unless the
    # open-loop path happens to be unbiased already, which it is not here)
    assert with_ef < without / 10


# ---------------------------------------------------------------------------
# hypothesis property tests (optional dev dep — fixed cases above always run)
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 1000), n=st.integers(1, 2000),
           scale=st.floats(1e-4, 1e3), codec=st.sampled_from(CODECS))
    def test_roundtrip_error_bound_property(seed, n, scale, codec):
        levels = {"int8": 127, "int4": 7}[codec]
        x = rand((n,), seed=seed, scale=scale)
        rt = codec_ops.codec_roundtrip_array(x, codec=codec, block=256)
        err = np.abs(np.asarray(x) - np.asarray(rt))
        half = np.repeat(_block_scales(x, 256, levels) * 0.5, 256)[: n]
        assert (err <= half * (1 + 1e-6) + 1e-9).all()

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 200), codec=st.sampled_from(CODECS))
    def test_error_feedback_bias_property(seed, codec):
        d = rand((1024,), seed=seed, scale=0.1)
        levels = {"int8": 127, "int4": 7}[codec]
        step = float(_block_scales(d, 256, levels).mean())
        assert _ef_bias(d, 16, codec, ef=True) < 2.0 * step / 16 + 1e-9
except ImportError:
    pass


# ---------------------------------------------------------------------------
# engine threading: bytes accounting + residual state
# ---------------------------------------------------------------------------


def _engine(codec="none", **kw):
    from test_engine_state import engine_for, perturb
    eng, stack = engine_for("cocodc", H=6, K=2, tau=2, wire_codec=codec, **kw)
    return eng, perturb(stack)


def test_codec_off_keeps_residual_out_of_state():
    """codec="none" must not grow the EngineState pytree (checkpoint layout
    and the traced program stay identical to the pre-codec engine)."""
    from repro.core import engine_state as es
    eng, _ = _engine("none")
    assert eng.state.wire_residual is None
    d = es.state_to_dict(eng.state)
    assert d["wire_residual"] is None
    assert es.state_from_dict(eng.state, d).wire_residual is None


@pytest.mark.parametrize("codec,floor", [("int8", 3.5), ("int4", 7.0)])
def test_engine_codec_shrinks_wire(codec, floor):
    """The scheduler's bytes/transfer accounting sees the compressed payload:
    raw/wire ratio clears the codec's floor and per-transfer time shrinks."""
    eng_n, s = _engine("none")
    eng_c, _ = _engine(codec)
    sn, sc = s, s
    for t in range(30):
        sn = eng_n.on_step_end(t, sn)
        sc = eng_c.on_step_end(t, sc)
    stn, stc = eng_n.stats(), eng_c.stats()
    assert stn["compression_ratio"] == 1.0
    assert stc["compression_ratio"] > floor
    assert stc["mean_transfer_s"] < stn["mean_transfer_s"]
    assert stc["wire_bytes_total"] < stc["wire_bytes_raw"]
    # residual buffers engaged and non-trivial after real initiations
    assert any(float(np.abs(np.asarray(l)).max()) > 0
               for l in jax.tree.leaves(eng_c.state.wire_residual))


def test_engine_codec_ef_off_has_no_residual():
    eng, s = _engine("int8", codec_error_feedback=False)
    for t in range(12):
        s = eng.on_step_end(t, s)
    assert eng.state.wire_residual is None
    assert eng.stats()["compression_ratio"] > 3.5


def test_pre_codec_engine_dict_restores_with_zero_residual():
    """A serialized EngineState written before the codec existed has no
    `wire_residual` entry: restoring into a codec-enabled engine restarts
    error feedback from the ref state's zero residual."""
    from repro.core import engine_state as es
    eng, s = _engine("int8")
    for t in range(12):
        s = eng.on_step_end(t, s)
    d = es.state_to_dict(eng.state)
    assert "wire_residual" in d
    d.pop("wire_residual")
    ref, _ = _engine("int8")            # freshly-initialized engine's state
    ref = ref.state
    restored = es.state_from_dict(ref, d)
    for l in jax.tree.leaves(restored.wire_residual):
        assert not np.asarray(l).any()
    # present key round-trips exactly
    full = es.state_from_dict(ref, es.state_to_dict(eng.state))
    for a, b in zip(jax.tree.leaves(full.wire_residual),
                    jax.tree.leaves(eng.state.wire_residual)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scheduler_state_v4_upgrades_wire_bytes_raw():
    """Pre-codec scheduler dicts (schema <= 4) carry no wire_bytes_raw: the
    upgrade path seeds it from bytes_sent (ratio resumes at 1.0)."""
    from repro.core.protocol import (SCHEDULER_SCHEMA_VERSION,
                                     upgrade_scheduler_state)
    eng, s = _engine("none")
    for t in range(12):
        s = eng.on_step_end(t, s)
    st = eng.scheduler_state()
    assert st["schema_version"] == SCHEDULER_SCHEMA_VERSION
    legacy = {k: v for k, v in st.items() if k != "wire_bytes_raw"}
    legacy["schema_version"] = 4
    up = upgrade_scheduler_state(legacy)
    assert up["wire_bytes_raw"] == st["bytes_sent"]
    assert up["schema_version"] == SCHEDULER_SCHEMA_VERSION
    eng.restore_scheduler(up)
    assert eng.stats()["compression_ratio"] == 1.0


# ---------------------------------------------------------------------------
# trainer: kill/resume with an active codec, spec plumbing
# ---------------------------------------------------------------------------


def _trainer(method="cocodc", steps=24, loop="segment", **ccfg_kw):
    mcfg = dataclasses.replace(get_config("paper_150m").reduced(),
                               compute_dtype="float32")
    ccfg = CoCoDCConfig(num_workers=2, local_steps=8, num_fragments=2,
                        overlap_depth=2, **ccfg_kw)
    tcfg = TrainerConfig(method=method, local_batch=2, seq_len=16,
                         total_steps=steps, warmup_steps=4, inner_lr=3e-3,
                         eval_batch=4, seed=0, loop=loop)
    return CrossRegionTrainer(mcfg, ccfg, tcfg)


def test_resume_mid_flight_with_active_codec(tmp_path):
    """Kill/resume with compressed fragments on the wire AND a live EF
    residual replays the reference run bitwise — the residual pytree and the
    wire_bytes_raw tally are part of the checkpoint."""
    ck = os.path.join(tmp_path, "ck.msgpack")
    ref = _trainer(wire_codec="int8")
    ref.run(eval_every=8, log=lambda s: None)

    tr = _trainer(wire_codec="int8", loop="per_step")
    while not tr.engine.pending:          # stop with a transfer on the wire
        tr.train_one_step()
    tr.save_checkpoint(ck)
    resumed = _trainer(wire_codec="int8").restore_checkpoint(ck)
    for a, b in zip(jax.tree.leaves(resumed.engine.state.wire_residual),
                    jax.tree.leaves(tr.engine.state.wire_residual)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    resumed.run(eval_every=8, log=lambda s: None)
    ra = {r["step"]: r["nll"] for r in ref.history}
    rb = {r["step"]: r["nll"] for r in resumed.history}
    assert set(rb) and all(ra[s] == rb[s] for s in sorted(set(ra) & set(rb)))
    sr, ss = ref.engine.stats(), resumed.engine.stats()
    assert sr["wire_bytes_raw"] == ss["wire_bytes_raw"]
    assert sr["bytes_sent"] == ss["bytes_sent"]
    assert sr["compression_ratio"] == ss["compression_ratio"] > 3.5


def test_codec_mismatch_rejected_on_resume(tmp_path):
    ck = os.path.join(tmp_path, "ck.msgpack")
    tr = _trainer(wire_codec="int8", steps=8)
    tr.run(eval_every=8, log=lambda s: None)
    tr.save_checkpoint(ck)
    with pytest.raises(ValueError, match="wire_codec"):
        _trainer(wire_codec="none", steps=8).restore_checkpoint(ck)


def test_spec_validates_codec_fields():
    from repro.api.spec import ExperimentSpec, MethodExtensions, MethodSpec

    def spec(**ext):
        return ExperimentSpec(method=MethodSpec(
            extensions=MethodExtensions(**ext)))

    spec(wire_codec="int8", codec_block=512).validate()
    with pytest.raises(ValueError, match="wire_codec"):
        spec(wire_codec="zstd").validate()
    with pytest.raises(ValueError, match="codec_block"):
        spec(wire_codec="int4", codec_block=9).validate()
    with pytest.raises(ValueError, match="codec_block"):
        spec(codec_block=0).validate()
    # knobs reach the protocol config
    from repro.api.spec import NetworkSpec
    cc = spec(wire_codec="int4", codec_block=512,
              codec_error_feedback=False).method.to_cocodc(NetworkSpec())
    assert (cc.wire_codec, cc.codec_block, cc.codec_error_feedback) == \
        ("int4", 512, False)


def test_stale_spec_hash_recomputed_from_stored_spec(tmp_path):
    """A checkpoint whose stored hash predates newer spec fields still
    resumes: the identity check re-hashes the SAVED spec dict with current
    code (defaults filled) before rejecting."""
    from repro.api import build_experiment
    from repro.api.spec import ExperimentSpec, ModelRef, RunSpec

    spec = ExperimentSpec(model=ModelRef(arch="paper_150m", reduced=True),
                          run=RunSpec(steps=8, seed=0)).validate()
    tr = build_experiment(spec)
    tr.run(eval_every=8, log=lambda s: None)
    ck = os.path.join(tmp_path, "ck.msgpack")
    tr.save_checkpoint(ck)
    from repro.checkpoint import load_pytree, save_pytree
    st = load_pytree(ck)
    assert st["meta"]["spec_hash"] == spec.spec_hash
    st["meta"]["spec_hash"] = "0" * 16          # hash from an older field set
    save_pytree(ck, st)
    build_experiment(spec).restore_checkpoint(ck)   # accepted via re-hash
    # a genuinely different spec still fails
    other = dataclasses.replace(spec, run=RunSpec(steps=8, seed=1)).validate()
    with pytest.raises(ValueError, match="spec"):
        build_experiment(other).restore_checkpoint(ck)


# ---------------------------------------------------------------------------
# the bitwise pin: wire_codec="none" reproduces the PR 5 trajectories
# ---------------------------------------------------------------------------

# Captured from the pre-codec tree (commit 24a7470) with _trainer() above:
# eval history [step, train_loss, nll], scheduler tallies, and f64 sums of
# the consensus model / worker stacks. Any drift here means the codec="none"
# path is no longer the bitwise-identical program it claims to be.
PR5_GOLDENS = {
    "diloco": {
        "history": [[8, 7.018250465393066, 6.6325154304504395],
                    [16, 6.345962047576904, 6.632944583892822],
                    [24, 6.365350723266602, 6.648122549057007]],
        "bytes_sent": 17316864.0, "n_syncs": 3.0, "wall_clock_s": 35.4,
        "theta_g_sum": 1197.9878458976746, "params_sum": 2395.9756712913513,
    },
    "streaming": {
        "history": [[8, 6.976778030395508, 6.653458833694458],
                    [16, 6.449089050292969, 6.618683815002441],
                    [24, 6.346522331237793, 6.56982946395874]],
        "bytes_sent": 17316864.0, "n_syncs": 6.0, "wall_clock_s": 24.0,
        "theta_g_sum": 1194.069115638733, "params_sum": 2381.7145833969116,
    },
    "cocodc": {
        "history": [[8, 6.994054794311523, 6.6532673835754395],
                    [16, 6.42584228515625, 6.615197420120239],
                    [24, 6.247212886810303, 6.607685804367065]],
        "bytes_sent": 17316864.0, "n_syncs": 6.0, "wall_clock_s": 24.0,
        "theta_g_sum": 1177.3517136573792, "params_sum": 2361.255774974823,
    },
    "local": {
        "history": [[8, 7.018250465393066, 6.685399770736694],
                    [16, 6.438072204589844, 6.685399770736694],
                    [24, 6.43746280670166, 6.685399770736694]],
        "bytes_sent": 0.0, "n_syncs": 0.0, "wall_clock_s": 24.0,
        "theta_g_sum": 1182.6093229055405, "params_sum": 2370.9805886745453,
    },
}


@pytest.mark.parametrize("method", sorted(PR5_GOLDENS))
def test_codec_none_pins_pr5_trajectory(method):
    tr = _trainer(method)       # wire_codec defaults to "none"
    tr.run(eval_every=8, log=lambda s: None)
    g = PR5_GOLDENS[method]
    got = [[r["step"], float(r["train_loss"]), float(r["nll"])]
           for r in tr.history]
    assert got == g["history"]
    st = tr.engine.stats()
    assert st["bytes_sent"] == g["bytes_sent"]
    assert st["n_syncs"] == g["n_syncs"]
    assert st["wall_clock_s"] == g["wall_clock_s"]
    assert st["wire_bytes_raw"] == g["bytes_sent"]     # raw == wire, no codec
    theta_sum = float(sum(np.float64(np.asarray(l).sum())
                          for l in jax.tree.leaves(tr.engine.theta_g)))
    params_sum = float(sum(np.float64(np.asarray(l).sum())
                           for l in jax.tree.leaves(tr.params_stack)))
    assert theta_sum == g["theta_g_sum"]
    assert params_sum == g["params_sum"]
