"""Cross-region trainer: M worker-stacked inner AdamW loops + a protocol engine
(DiLoCo / Streaming DiLoCo / CoCoDC) coordinating cross-region synchronization.

Worker-local params/optimizer/batches carry a leading worker axis M; the inner
train step is vmapped over it (on the multi-pod mesh this axis is sharded over
`pod`, making each pod a datacenter — see launch/). The engine is host-side
scheduling around jitted device ops, exactly the structure of a real deployment's
coordinator process.

Execution engine (segment-scanned): the host loop iterates over PROTOCOL EVENTS,
not steps. All inner steps between consecutive events (fragment initiations,
deliveries, DiLoCo rounds) run as ONE jitted `lax.scan` over a prefetched
stacked batch segment (`SegmentRunner`), so N steps cost one dispatch instead of
N — the WAN-hiding structure of Streaming DiLoCo/CoCoDC maps onto long pure
segments punctuated by sparse syncs. `loop="per_step"` keeps the legacy
one-dispatch-per-step path for golden-trajectory parity tests and debugging.

Checkpoint/resume: the full run state — `TrainerState` pytree (params stack,
inner optimizer, EngineState, step/wall-clock/data cursor) plus the host
scheduler (in-flight transfers, WAN channel clocks, traffic matrices) and the
eval history — round-trips atomically through checkpoint/io at any segment
boundary; a resumed run replays the exact trajectory of an uninterrupted one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.analysis.retrace import RetraceSentinel
from repro.checkpoint.io import load_pytree, restore_like, save_pytree
from repro.configs.base import CoCoDCConfig, ModelConfig
from repro.core import engine_state as es
from repro.core.fragments import make_fragmenter
from repro.core.network import (NetworkModel, Topology, apply_dynamics,
                                as_topology, paper_network)
from repro.core.protocol import ProtocolEngine
from repro.data.pipeline import (MarkovCorpus, make_worker_streams,
                                 stacked_batch, stacked_segment)
from repro.models import api
from repro.optim import adamw_init, adamw_update, warmup_cosine
from repro.optim.adamw import AdamWState
from repro.telemetry import Counters, kernel_notes, span


@dataclasses.dataclass
class TrainerConfig:
    method: str = "cocodc"              # diloco | streaming | cocodc | local
    local_batch: int = 8
    seq_len: int = 64
    total_steps: int = 400
    inner_lr: float = 4e-4
    warmup_steps: int = 50
    weight_decay: float = 0.1
    eval_batch: int = 16
    seed: int = 0
    noniid_frac: float = 0.25
    # "jit" = functional EngineState transitions under jax.jit (hot path);
    # "host" = same pure functions executed eagerly (legacy-equivalent path,
    # kept for golden-trajectory parity tests and debugging)
    engine_impl: str = "jit"
    # "segment" = fuse all inner steps between protocol events under one jitted
    # lax.scan (hot path); "per_step" = one dispatch per step (legacy path,
    # kept for golden-trajectory parity tests and debugging)
    loop: str = "segment"
    # longest fused segment (bounds the prefetched batch stack for event-free
    # stretches, e.g. method="local"); power of two keeps the chunked scan's
    # compiled-program set minimal
    max_segment: int = 64


@dataclasses.dataclass
class TrainerState:
    """Everything device-side a resumed run needs, as one pytree: worker-stacked
    params + inner AdamW state, the protocol EngineState, and the scalar run
    cursors. Host-side scheduler state (in-flight transfer schedule, channel
    clocks, traffic matrices) rides alongside in the checkpoint dict — see
    `CrossRegionTrainer.checkpoint_state`."""
    params_stack: Any
    opt_state: Any
    engine: es.EngineState
    step: int
    wall_clock: float
    data_cursor: int    # == step (data is a pure fn of step) — kept explicit
                        # so a future stateful loader has a slot to fill


jax.tree_util.register_dataclass(
    TrainerState,
    data_fields=[f.name for f in dataclasses.fields(TrainerState)],
    meta_fields=[])


CKPT_FORMAT = "trainer_state_v1"

# Checkpoint-meta schema. One upgrade path (`CrossRegionTrainer._upgrade_meta`)
# replaces the scattered `.get(..., default)` back-compat reads:
#   v1 — ad-hoc per-key trajectory meta (keys accreted over PRs 2-4)
#   v2 (PR 5) — + schema_version stamp, spec dict + spec_hash (primary
#     resume validation for spec-built trainers)
#   v3 (PR 6) — + wire-codec trajectory knobs (wire_codec, codec_block,
#     codec_error_feedback); pre-codec checkpoints upgrade to "none"
#   v4 (PR 7) — + traffic-plane knobs (channel_scheduler, multipath_k);
#     pre-fairshare checkpoints upgrade to the serial channel queue
#   v5 (PR 8) — + fused_updates (flat-plane engine buffers change the
#     in-flight/residual state SHAPES, so cross-mode resume must be
#     rejected up front, not die in restore_like); pre-fused checkpoints
#     upgrade to the per-leaf path
META_SCHEMA_VERSION = 5


@functools.lru_cache(maxsize=None)
def _jit_gen_frames():
    """Audio-stub frame segments in one dispatch: vmap the per-step folded-key
    generator over the step axis (rows are invariant to the padded length, so
    the per-step and segment paths share one generator and stay bitwise-equal)."""
    def gen(root_key, steps, batch, n_prefix, dim):
        def one(step):
            key = jax.random.fold_in(root_key, step)
            return jax.random.normal(key, (batch, n_prefix, dim),
                                     jnp.float32) * 0.1
        return jax.vmap(one)(steps)
    return jax.jit(gen, static_argnums=(2, 3, 4))


def make_single_step(mcfg: ModelConfig, *, weight_decay: float,
                     attn_impl: str = "auto"):
    """One worker's inner step: loss + grad of `api.loss_fn`, then AdamW.
    `(params, opt_state, batch, lr) -> (params, opt_state, loss)`; the
    trainer vmaps it over the worker axis (and `SegmentRunner` scans it).
    `attn_impl` goes through `api.resolve_attn_impl`: the flash kernel
    with its backward pass on accelerators, the reference on CPU."""
    attn_impl = api.resolve_attn_impl(attn_impl)

    def single_step(params, opt_state, batch, lr):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: api.loss_fn(mcfg, p, batch, attn_impl=attn_impl),
            has_aux=True)(params)
        params, opt_state = adamw_update(grads, opt_state, params, lr,
                                         weight_decay=weight_decay)
        return params, opt_state, loss

    return single_step


class SegmentRunner:
    """Fused inner-step executor: scans `single_step` (vmapped over the worker
    axis) across a stacked batch segment, carrying (params_stack, opt_state)
    and consuming a per-step LR array.

    The jit cache retraces per distinct scan length, and protocol event gaps
    vary (queueing shifts deliveries, Eq. 11 shifts initiations), so a raw
    per-length cache would recompile all run long. Segments are therefore
    dispatched as DESCENDING POWER-OF-TWO chunks (13 -> 8+4+1): the compiled-
    program set is bounded by log2(max segment), and since quiet steps carry no
    protocol interaction, the chunked scan is bitwise-identical to one fused
    scan (and to the per-step loop — pinned by tests/test_trainer_segments).

    On non-CPU backends the scan carry (params stack + inner optimizer) is
    DONATED to each chunk dispatch, so the buffers are updated in place instead
    of being copied per chunk; the caller always rebinds to the returned carry.
    CPU jit does not support donation (XLA warns and ignores it), so the flag
    is gated on the backend (`donate` overrides the gate — used by the
    static-analysis donation audit to inspect the accelerator wiring).

    Each call is the host span `repro.dispatch` (chunk slicing, the scan
    dispatches, the loss concat); every chunk counts one scan dispatch in
    `telemetry`, the trainer's `Counters` when it built the runner. Each
    program traced whose layer body took the attention kernel counts one
    `attention_kernel_layers`.

    The power-of-two contract is ENFORCED, not just relied on: the jitted
    scan is wrapped in a `RetraceSentinel` with budget log2(max_segment)+1
    (one compiled program per chunk length 1, 2, ..., max_segment), so an
    event-gap-induced recompile beyond that set fails loudly at the call
    that caused it instead of silently recompiling all run long."""

    DONATE_ARGNUMS = (0, 1)              # params_stack, opt_state (scan carry)

    def __init__(self, single_step, *, max_segment: int = 64,
                 donate: bool | None = None,
                 telemetry: Optional[Counters] = None):
        self.single_step = single_step
        self.max_segment = int(max_segment)
        self.telemetry = Counters() if telemetry is None else telemetry
        vstep = jax.vmap(single_step, in_axes=(0, 0, 0, None))

        def run_segment(params_stack, opt_state, batch_seg, lrs):
            def body(carry, xs):
                batch, lr = xs
                p, o, losses = vstep(carry[0], carry[1], batch, lr)
                return (p, o), losses

            with kernel_notes() as notes:
                (p, o), losses = jax.lax.scan(
                    body, (params_stack, opt_state), (batch_seg, lrs))
            if "attention" in notes:
                self.telemetry.attention_kernel_layers += 1
            return p, o, losses          # losses: (n, M)

        can_donate = (jax.default_backend() != "cpu" if donate is None
                      else donate)
        self._fn = RetraceSentinel(
            jax.jit(run_segment,
                    donate_argnums=self.DONATE_ARGNUMS if can_donate else ()),
            name="trainer.segment_scan",
            max_traces=max(1, self.max_segment.bit_length()))

    @property
    def trace_count(self) -> int:
        return self._fn.trace_count

    def __call__(self, params_stack, opt_state, batch_seg, lrs):
        n = int(lrs.shape[0])
        with span("dispatch", chunks=bin(n).count("1")):
            loss_chunks = []
            i = 0
            while i < n:
                c = 1 << ((n - i).bit_length() - 1)   # largest 2^k <= n - i
                chunk = jax.tree.map(lambda x: x[i:i + c], batch_seg)
                params_stack, opt_state, losses = self._fn(
                    params_stack, opt_state, chunk, lrs[i:i + c])
                self.telemetry.scan_dispatched()
                loss_chunks.append(losses)
                i += c
            losses = (loss_chunks[0] if len(loss_chunks) == 1
                      else jnp.concatenate(loss_chunks))
        return params_stack, opt_state, losses


class CrossRegionTrainer:
    def __init__(self, model_cfg: ModelConfig, ccfg: CoCoDCConfig,
                 tcfg: TrainerConfig,
                 network: Optional["NetworkModel | Topology"] = None,
                 dynamics: Optional[str] = None, dynamics_seed: int = 0,
                 spec: Optional[Any] = None):
        self.mcfg = model_cfg
        self.ccfg = ccfg
        self.tcfg = tcfg
        # the declarative ExperimentSpec this trainer was built from
        # (repro.api.build_experiment); None when constructed directly.
        # Rides into checkpoints as meta["spec"]/meta["spec_hash"] — the
        # primary resume-identity check.
        self.spec = spec
        M = ccfg.num_workers
        # host spans' counters (repro.telemetry): never saved, never in history
        self.telemetry = Counters()

        key = jax.random.PRNGKey(tcfg.seed)
        params = api.init_params(model_cfg, key)
        self.params_stack = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (M,) + a.shape).copy(), params)
        self.opt_state = jax.vmap(adamw_init)(self.params_stack)

        shape = jax.eval_shape(lambda: params)
        self.fragmenter = make_fragmenter(model_cfg, shape, ccfg.num_fragments,
                                          strided=ccfg.strided_fragments,
                                          strategy=ccfg.fragment_strategy)
        if network is None:
            network = paper_network(
                M, fragment_bytes=self.fragmenter.total_bytes // ccfg.num_fragments,
                tau=ccfg.overlap_depth)
        if dynamics:
            # time-varying links apply to ANY base topology, incl. the
            # calibrated symmetric default (seeded -> deterministic resume)
            network = apply_dynamics(as_topology(network), dynamics,
                                     seed=dynamics_seed)
        self.network = network
        self.engine = ProtocolEngine(tcfg.method, ccfg, self.fragmenter, network,
                                     self.params_stack,
                                     engine_impl=tcfg.engine_impl,
                                     telemetry=self.telemetry)

        self.streams = make_worker_streams(M, model_cfg.vocab, seed=tcfg.seed,
                                           noniid_frac=tcfg.noniid_frac)
        # held-out IID stream (global backbone) for consensus-model evaluation
        self.eval_stream = MarkovCorpus(vocab=model_cfg.vocab, seed=tcfg.seed,
                                        worker_id=-1, noniid_frac=0.0)
        # frame RNG for the audio stub frontend: per-step keys are folded off
        # this root, never constructed from raw step arithmetic
        self._frame_key = jax.random.PRNGKey(0x5EED)

        mcfg = model_cfg
        single_step = make_single_step(mcfg, weight_decay=tcfg.weight_decay)
        self._train_step = jax.jit(jax.vmap(single_step,
                                            in_axes=(0, 0, 0, None)))
        self.segment_runner = SegmentRunner(single_step,
                                            max_segment=tcfg.max_segment,
                                            telemetry=self.telemetry)

        def eval_loss(params, batch):
            loss, metrics = api.loss_fn(mcfg, params, batch)
            return metrics["nll"]

        self._eval = jax.jit(eval_loss)
        self.history: List[Dict] = []
        self.step = 0

    def lr(self, step):
        """Inner LR at `step` — accepts a scalar or a per-step array."""
        return warmup_cosine(step, base_lr=self.tcfg.inner_lr,
                             warmup_steps=self.tcfg.warmup_steps,
                             total_steps=self.tcfg.total_steps)

    # ------------------------------------------------------- data + frontends

    def _augment(self, batch, step, stacked: bool):
        """Add stub-frontend inputs for the audio family. Uses the SAME jitted
        generator as the segment path (a normal() computed eagerly vs under
        jit/vmap differs in the last ulp, which would break scanned-vs-per-step
        bitwise parity)."""
        if self.mcfg.family != "audio":
            return batch
        B = batch["tokens"].shape[-2]
        frames = _jit_gen_frames()(self._frame_key, jnp.asarray([step]), B,
                                   self.mcfg.n_prefix_tokens,
                                   self.mcfg.prefix_dim)[0]
        if stacked:
            M = batch["tokens"].shape[0]
            frames = jnp.broadcast_to(frames[None], (M,) + frames.shape)
        return dict(batch, frames=frames)

    def _augment_segment(self, batch_seg, t0: int, n: int):
        """Per-step frames stacked step-major: (n, M, B, P, D) — matches
        `_augment(..., stacked=True)` at each step of the segment, generated
        in ONE dispatch (power-of-two padded like the data segments)."""
        if self.mcfg.family != "audio":
            return batch_seg
        M, B = batch_seg["tokens"].shape[1], batch_seg["tokens"].shape[2]
        m = 1 << max(0, n - 1).bit_length()
        steps = jnp.arange(t0, t0 + m)
        frames = _jit_gen_frames()(self._frame_key, steps, B,
                                   self.mcfg.n_prefix_tokens,
                                   self.mcfg.prefix_dim)[:n]
        frames = jnp.broadcast_to(frames[:, None],
                                  (n, M) + frames.shape[1:])
        return dict(batch_seg, frames=frames)

    # -------------------------------------------------------------- stepping

    def train_one_step(self):
        """Legacy per-step path: one dispatch per inner step (loop="per_step").
        The scanned path must reproduce this trajectory exactly — pinned by
        tests/test_trainer_segments.py."""
        t = self.step
        batch = stacked_batch(self.streams, t, self.tcfg.local_batch,
                              self.tcfg.seq_len)
        batch = self._augment(batch, t, stacked=True)
        self.params_stack, self.opt_state, losses = self._train_step(
            self.params_stack, self.opt_state, batch, self.lr(t))
        self.params_stack = self.engine.on_step_end(t, self.params_stack)
        self.step += 1
        self.telemetry.steps += 1
        return float(self.telemetry.read("loss", jnp.mean(losses)))

    def _run_segment(self, t0: int, n: int) -> float:
        """Run steps [t0, t0+n) as one scanned dispatch. The segment is chosen
        so only its LAST step can be a protocol event; quiet steps advance the
        simulated wall-clock without touching the engine. Returns the mean
        loss of the last step, which BLOCKS on the whole segment (host sync
        `loss`)."""
        with span("segment", t0=t0, n=n):
            with span("data", t0=t0, n=n):
                batch_seg = stacked_segment(self.streams, t0, n,
                                            self.tcfg.local_batch,
                                            self.tcfg.seq_len)
                batch_seg = self._augment_segment(batch_seg, t0, n)
            with span("lr"):
                lrs = self.lr(jnp.arange(t0, t0 + n))
            self.params_stack, self.opt_state, losses = self.segment_runner(
                self.params_stack, self.opt_state, batch_seg, lrs)
            if n > 1:
                self.engine.advance_steps(n - 1)
            self.params_stack = self.engine.on_step_end(t0 + n - 1,
                                                        self.params_stack)
            self.step = t0 + n
            self.telemetry.segments += 1
            self.telemetry.steps += n
            return float(self.telemetry.read("loss", jnp.mean(losses[-1])))

    def _segment_end(self, t: int, target: int, eval_every: int,
                     ckpt_every: int) -> int:
        """Last step (inclusive) of the segment starting at t: the earliest of
        the next protocol event, the next eval/checkpoint boundary, and the end
        of the run (host span `repro.plan`)."""
        with span("plan", t0=t):
            end = min(target - 1, t + self.tcfg.max_segment - 1)
            ne = self.engine.next_event_step(t)
            if ne is not None:
                end = min(end, ne)
            for every in (eval_every, ckpt_every):
                if every:
                    end = min(end, (t // every + 1) * every - 1)
            return end

    # ------------------------------------------------------------------ eval

    def evaluate(self, n_batches: int = 2) -> Dict[str, float]:
        """Perplexity of the consensus (global) model on the held-out stream."""
        theta = self.engine.theta_g
        nll = 0.0
        for i in range(n_batches):
            batch = self.eval_stream.batch(10_000_000 + i, self.tcfg.eval_batch,
                                           self.tcfg.seq_len)
            batch = self._augment(batch, 10_000_000 + i, stacked=False)
            nll += float(self.telemetry.read("eval", self._eval(theta, batch)))
        nll /= n_batches
        return {"nll": nll,
                "ppl": float(self.telemetry.read("eval", jnp.exp(nll)))}

    # ------------------------------------------------------------------- run

    def _record_eval(self, train_loss: float, log: Callable[[str], None]):
        ev = self.evaluate()
        rec = {"step": self.step, "train_loss": train_loss, **ev,
               **self.engine.stats()}
        self.history.append(rec)
        log(f"[{self.tcfg.method}] step {self.step:5d} "
            f"train {train_loss:.4f} eval_nll {ev['nll']:.4f} "
            f"ppl {ev['ppl']:.2f} wall {self.engine.wall_clock:.0f}s")

    def run(self, steps: Optional[int] = None, eval_every: int = 50,
            log: Callable[[str], None] = lambda s: None,
            ckpt_path: Optional[str] = None, ckpt_every: int = 0):
        """Train to absolute step `steps` (default tcfg.total_steps) — a resumed
        trainer continues from its restored cursor. With ckpt_path/ckpt_every,
        atomically checkpoints the full run state at those segment boundaries."""
        target = steps if steps is not None else self.tcfg.total_steps
        if self.tcfg.loop == "per_step":
            while self.step < target:
                train_loss = self.train_one_step()
                if self.step % eval_every == 0 or self.step == target:
                    self._record_eval(train_loss, log)
                if (ckpt_path and ckpt_every and self.step % ckpt_every == 0):
                    self.save_checkpoint(ckpt_path)
            return self.history

        while self.step < target:
            t0 = self.step
            end = self._segment_end(t0, target, eval_every, ckpt_every)
            train_loss = self._run_segment(t0, end - t0 + 1)
            if self.step % eval_every == 0 or self.step == target:
                self._record_eval(train_loss, log)
            if ckpt_path and ckpt_every and self.step % ckpt_every == 0:
                self.save_checkpoint(ckpt_path)
        return self.history

    def steps_to_ppl(self, target: float) -> Optional[int]:
        for rec in self.history:
            if rec["ppl"] <= target:
                return rec["step"]
        return None

    # ---------------------------------------------------------- checkpointing

    def trainer_state(self) -> TrainerState:
        return TrainerState(
            params_stack=self.params_stack,
            opt_state=self.opt_state,
            engine=self.engine.state,
            step=self.step,
            wall_clock=float(self.engine.wall_clock),
            data_cursor=self.step,
        )

    def checkpoint_state(self) -> Dict[str, Any]:
        """Full-run checkpoint payload: TrainerState pytree (as plain field
        dicts — msgpack-safe), the host scheduler, eval history, and identity
        metadata for resume validation."""
        ts = self.trainer_state()
        meta = {"schema_version": META_SCHEMA_VERSION,
                "arch": self.mcfg.name, **self._traj_meta()}
        if self.spec is not None:
            meta["spec"] = self.spec.to_dict()
            meta["spec_hash"] = self.spec.spec_hash
        return {
            "format": CKPT_FORMAT,
            "trainer_state": {
                "params_stack": ts.params_stack,
                "opt_state": {"mu": ts.opt_state.mu, "nu": ts.opt_state.nu,
                              "count": ts.opt_state.count},
                "engine": es.state_to_dict(ts.engine),
                "step": ts.step,
                "wall_clock": ts.wall_clock,
                "data_cursor": ts.data_cursor,
            },
            "scheduler": self.engine.scheduler_state(),
            "history": self.history,
            "meta": meta,
        }

    def _traj_meta(self) -> Dict[str, Any]:
        """Every config knob the trajectory is a function of (data streams, LR
        schedule, protocol event schedule) — saved in the checkpoint and
        validated on resume so a mismatched resume errors instead of silently
        diverging."""
        t, c = self.tcfg, self.ccfg
        return {"method": t.method, "seed": t.seed, "total_steps": t.total_steps,
                "warmup_steps": t.warmup_steps, "inner_lr": t.inner_lr,
                "weight_decay": t.weight_decay, "local_batch": t.local_batch,
                "seq_len": t.seq_len, "noniid_frac": t.noniid_frac,
                "num_workers": c.num_workers, "local_steps": c.local_steps,
                "num_fragments": c.num_fragments,
                "overlap_depth": c.overlap_depth,
                "fragment_strategy": self.fragmenter.strategy,
                "routing": c.routing, "hub_failover": c.hub_failover,
                "adaptive_resync": c.adaptive_resync,
                "wire_codec": c.wire_codec, "codec_block": c.codec_block,
                "codec_error_feedback": c.codec_error_feedback,
                "channel_scheduler": c.channel_scheduler,
                "multipath_k": c.multipath_k,
                "fused_updates": c.fused_updates}

    def _upgrade_meta(self, meta: Dict[str, Any]) -> Dict[str, Any]:
        """Single upgrade path for checkpoint meta of any prior schema
        version (the meta twin of `protocol.upgrade_scheduler_state`). A key
        a v1 checkpoint predates implies whatever the key-less code did with
        THIS config: pre-PR3 fragmentation came from strided_fragments;
        pre-PR4 runs had no routed planner or Eq. 9 re-derivation; pre-PR5
        runs carried no spec."""
        meta = dict(meta)
        meta.setdefault("fragment_strategy",
                        "strided" if self.ccfg.strided_fragments
                        else "contiguous")
        meta.setdefault("routing", "static")
        meta.setdefault("hub_failover", False)
        meta.setdefault("adaptive_resync", False)
        meta.setdefault("spec", None)
        meta.setdefault("spec_hash", None)
        # pre-PR6 checkpoints predate the wire codec: raw f32/sync_dtype wire
        meta.setdefault("wire_codec", "none")
        meta.setdefault("codec_block", 256)
        meta.setdefault("codec_error_feedback", True)
        # pre-PR7 checkpoints predate the traffic plane: serial channel queue
        meta.setdefault("channel_scheduler", "serial")
        meta.setdefault("multipath_k", 1)
        # pre-PR8 checkpoints predate the fused engine: per-leaf buffers
        meta.setdefault("fused_updates", False)
        meta["schema_version"] = META_SCHEMA_VERSION
        return meta

    def _validate_resume_identity(self, meta: Dict[str, Any]):
        """Reject a resume whose run identity differs from this trainer's.
        Spec-built trainers compare `spec_hash` (the digest of every
        trajectory-determining spec field); the error names the differing
        fields. Directly-constructed trainers (and pre-spec checkpoints)
        fall back to the per-key trajectory-meta comparison."""
        if self.spec is not None and meta["spec_hash"] is not None:
            if meta["spec_hash"] == self.spec.spec_hash:
                return
            if isinstance(meta["spec"], dict):
                from repro.api.spec import ExperimentSpec
                try:
                    # a checkpoint written before newer spec fields existed
                    # stores a hash over the field-less dict; re-hashing the
                    # SAVED spec with current code fills the new defaults, so
                    # a match proves the stored run is trajectory-identical
                    if ExperimentSpec.from_dict(
                            meta["spec"]).spec_hash == self.spec.spec_hash:
                        return
                except ValueError:
                    pass
            detail = ""
            if isinstance(meta["spec"], dict):
                from repro.api.spec import (_VOLATILE_RUN_FIELDS,
                                            ExperimentSpec, diff_specs)
                try:
                    saved = ExperimentSpec.from_dict(meta["spec"]).traj_dict()
                except ValueError:
                    # e.g. a checkpoint from a newer version with unknown
                    # spec fields: diff the raw dict, but strip the labels
                    # and volatile run fields traj_dict() excludes so the
                    # message only names genuine trajectory differences
                    saved = {k: v for k, v in meta["spec"].items()
                             if k not in ("name", "note")}
                    if isinstance(saved.get("run"), dict):
                        run = {k: v for k, v in saved["run"].items()
                               if k not in _VOLATILE_RUN_FIELDS}
                        # mirror RunSpec.resolved_warmup so a defaulted
                        # warmup is not reported as a spurious diff
                        if run.get("warmup_steps") is None and \
                                isinstance(run.get("steps"), int):
                            run["warmup_steps"] = max(10, run["steps"] // 20)
                        saved["run"] = run
                diffs = diff_specs(saved, self.spec.traj_dict())
                detail = "; differing fields: " + "; ".join(diffs)
            raise ValueError(
                f"checkpoint was written by a different experiment spec "
                f"(spec_hash {meta['spec_hash']} != {self.spec.spec_hash})"
                f"{detail}")
        for k, want in (("arch", self.mcfg.name), *self._traj_meta().items()):
            if meta.get(k) != want:
                raise ValueError(
                    f"checkpoint {k}={meta.get(k)!r} != trainer {want!r} — "
                    f"resume requires the saved run's config (data streams, "
                    f"LR schedule, and the protocol event schedule derive "
                    f"from it)")

    def save_checkpoint(self, path: str):
        save_pytree(path, self.checkpoint_state())

    def restore_checkpoint(self, path: str, state: Optional[Dict] = None):
        """Restore a `checkpoint_state` dump into this (freshly built) trainer.
        The trainer must have been constructed with the same model/protocol
        configs; the restored run continues bit-for-bit where the saved one
        stopped (pinned by tests/test_checkpoint.py kill-and-resume). Pass
        `state` if the checkpoint is already deserialized (avoids a second
        full read of a multi-GB file)."""
        st = load_pytree(path) if state is None else state
        if st.get("format") != CKPT_FORMAT:
            raise ValueError(f"not a {CKPT_FORMAT} checkpoint: {path}")
        self._validate_resume_identity(self._upgrade_meta(st["meta"]))
        ts = st["trainer_state"]
        self.params_stack = restore_like(self.params_stack, ts["params_stack"])
        self.opt_state = AdamWState(
            mu=restore_like(self.opt_state.mu, ts["opt_state"]["mu"]),
            nu=restore_like(self.opt_state.nu, ts["opt_state"]["nu"]),
            count=restore_like(self.opt_state.count, ts["opt_state"]["count"]))
        self.engine.state = es.state_from_dict(self.engine.state, ts["engine"])
        self.engine.restore_scheduler(st["scheduler"])
        # TrainerState is the single authority for the run cursors
        self.engine.wall_clock = float(ts["wall_clock"])
        self.step = int(ts["step"])
        if int(ts["data_cursor"]) != self.step:
            raise ValueError(
                f"checkpoint data_cursor={ts['data_cursor']} != "
                f"step={self.step} (stateful loaders are not supported yet)")
        self.history = [
            {k: (v.item() if getattr(v, "shape", None) == () else v)
             for k, v in rec.items()} for rec in st["history"]]
        return self
