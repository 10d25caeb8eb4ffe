"""A training cell: CoCoDC over M vmapped regions, driven through the
trainer's own segment loop, and checked against the plain reference.

Set-up builds one `CrossRegionTrainer` from the seed and drives it through
its first steps up to the `checked_deliveries`-th fragment delivery (the
readings for `correct`), then on until every fragment has been initiated and
delivered once, so every program the window runs is loaded. The window then
runs the same object's segment loop (the body of `CrossRegionTrainer.run`
without eval and checkpoints) for the given seconds.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import time
from typing import Dict, List

import numpy as np

from benchmarks.chip import flops, reference

B1 = 0.9                              # the inner AdamW's first-moment decay


def program_seed(seed: int) -> int:
    """The program's seeds are int32 and its data streams add up to
    7919 * (M + 1) to them: fold any 64-bit seed into [0, 2**31 - 2**20)."""
    return int(seed) % (2 ** 31 - 2 ** 20)


def _override(obj, overrides: Dict):
    """`dataclasses.replace` of `obj` with `overrides`, where a nested object
    replaces fields of a dataclass field (`{"moe": {"num_experts": 10}}`)."""
    fields = {}
    for key, value in overrides.items():
        if isinstance(value, dict):
            inner = getattr(obj, key, None)
            if not dataclasses.is_dataclass(inner):
                raise ValueError(f"override {key!r}: {inner!r} has no fields")
            value = _override(inner, value)
        fields[key] = value
    return dataclasses.replace(obj, **fields)


_MISSING = object()


def _field(obj, path: str):
    """The value at a dotted attribute path, or `_MISSING`."""
    for part in path.split("."):
        obj = getattr(obj, part, _MISSING) if obj is not None else _MISSING
        if obj is _MISSING:
            return _MISSING
    return obj


def model_config(cfg: Dict, model=reference):
    """The program's ModelConfig for a configuration file, checked against
    every field the reference model fixes (`model.program_fields`), so the
    program and the reference run one model."""
    from repro.configs import get_config
    mcfg = _override(get_config(cfg["arch"]), cfg["overrides"])
    want = model.program_fields(cfg)
    got = {k: _field(mcfg, k) for k in want}
    unknown = [k for k, v in got.items() if v is _MISSING]
    if unknown:
        raise ValueError(f"{cfg['name']}: the program's config has no "
                         f"field {unknown}")
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"{cfg['name']}: program and file disagree "
                         f"(field: (program, file)) {bad}")
    if mcfg.compute_dtype != cfg["torch_dtype"] or \
            mcfg.param_dtype != cfg["param_dtype"]:
        raise ValueError(f"{cfg['name']}: dtypes differ from the file")
    return mcfg


def build_trainer(mcfg, mix: Dict, seed: int):
    from repro.api.spec import ExperimentSpec, MethodSpec, RunSpec
    from repro.core.trainer import CrossRegionTrainer
    spec = ExperimentSpec(
        method=MethodSpec(
            name=mix["method"], num_workers=mix["num_workers"],
            local_steps=mix["local_steps"],
            num_fragments=mix["num_fragments"],
            overlap_depth=mix["overlap_depth"],
            net_utilization=mix["net_utilization"],
            comp_lambda=mix["comp_lambda"], outer_lr=mix["outer_lr"],
            outer_momentum=mix["outer_momentum"]),
        run=RunSpec(steps=mix["total_steps"], seed=program_seed(seed),
                    local_batch=mix["local_batch"], seq_len=mix["seq_len"],
                    inner_lr=mix["inner_lr"],
                    warmup_steps=mix["warmup_steps"],
                    weight_decay=mix["weight_decay"],
                    noniid_frac=mix["noniid_frac"]))
    return CrossRegionTrainer(mcfg, spec.method.to_cocodc(spec.network),
                              spec.run.to_trainer_config(mix["method"]))


class Recorder:
    """Sits in the trainer's place of its SegmentRunner: passes every call
    through, keeps each segment's per-step losses (n, M), and while
    `keep_batches` is set the batch segments it was fed."""

    def __init__(self, runner):
        self.runner = runner
        self.call = runner
        self.keep_batches = True
        self.batches: List[Dict] = []
        self.losses: List = []

    @property
    def trace_count(self) -> int:
        return self.runner.trace_count

    def __call__(self, params_stack, opt_state, batch_seg, lrs):
        out = self.call(params_stack, opt_state, batch_seg, lrs)
        if self.keep_batches:
            self.batches.append({k: np.asarray(v) for k, v in
                                 batch_seg.items()})
        self.losses.append(out[2])
        return out


class Protocol:
    """Host-side record of the protocol's initiations and deliveries, read
    from the engine's in-flight list around each segment: `syncs` holds
    (fragment, t_init, t_deliver) of every delivered fragment."""

    def __init__(self, engine):
        self.engine = engine
        self.initiated: set = set()
        self.delivered: set = set()
        self.syncs: List = []
        self.n_init = 0

    @property
    def n_deliver(self) -> int:
        return len(self.syncs)

    def around(self, run, t_end: int):
        before = {id(p): (p.frag, p.t_init) for p in self.engine.pending}
        run()
        after = {id(p): p.frag for p in self.engine.pending}
        for k, (f, t_init) in before.items():
            if k not in after:
                self.delivered.add(f)
                self.syncs.append((f, t_init, t_end))
        for k, f in after.items():
            if k not in before:
                self.initiated.add(f)
                self.n_init += 1


def segment(trainer, proto: Protocol, target: int) -> int:
    """One segment of the trainer's own loop toward step `target`: it ends
    at the next protocol event (or `max_segment`, or `target`). Returns the
    steps it ran."""
    t0 = trainer.step
    end = trainer._segment_end(t0, target, 0, 0)
    proto.around(lambda: trainer._run_segment(t0, end - t0 + 1), end)
    return end - t0 + 1


def drive(trainer, proto: Protocol, target: int) -> int:
    """Segments until step `target`. Returns the steps run."""
    done = 0
    while trainer.step < target:
        done += segment(trainer, proto, target)
    return done


def _leaf_norms(tree):
    """{path: norms per region} of a pytree with a leading region axis."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): np.asarray(jnp.sqrt(jnp.sum(
        jnp.square(a.astype(jnp.float32)), axis=tuple(range(1, a.ndim)))))
        for p, a in flat}


def worst_leaf_gap(prog: Dict, ref: Dict, ref_grad: Dict) -> Dict:
    """Largest |norm_prog - norm_ref| over leaves and regions, against the
    larger of that leaf's reference norm and the median leaf's. Leaves whose
    reference gradient is under a thousandth of the median leaf's are left
    out (they move by round-off alone under Adam)."""
    worst, where, skipped = 0.0, None, []
    n_regions = len(next(iter(ref.values())))
    for m in range(n_regions):
        med = float(np.median([ref[k][m] for k in ref]))
        gmed = float(np.median([ref_grad[k][m] for k in ref_grad]))
        for k in ref:
            if ref_grad[k][m] < 1e-3 * gmed:
                skipped.append(k)
                continue
            gap = abs(float(prog[k][m]) - float(ref[k][m])) / max(
                float(ref[k][m]), med)
            if gap > worst:
                worst, where = gap, f"{k}[region {m}]"
    return {"gap": worst, "leaf": where, "skipped": sorted(set(skipped))}


def reference_readings(model, cfg: Dict, mix: Dict, seed: int,
                       batches: List, syncs: List, marks, **fault) -> Dict:
    """The reference model's losses, first-gradient norms and change norms
    after each step count in `marks`, from weights made from the seed,
    through the same protocol events."""
    import jax
    params0 = jax.jit(lambda k: model.init_params(cfg, k))(
        jax.random.PRNGKey(program_seed(seed)))
    return reference.train(model, cfg, params0, batches, mix, syncs, marks,
                           **fault)


def compare(prog: Dict, ref: Dict, n_checked: int, n_synced: int
            ) -> Dict[str, float]:
    """The numbers `correct` holds against its limits: the widest loss gap
    over every checked step, and the worst leaf's norm gap of the first
    gradient, of the change after `n_checked` steps, and of the change
    after `n_synced` steps, which ends with a fragment's delivery."""
    g = ref["grad"]
    return {
        "loss_gap": float(np.max(np.abs(prog["losses"] - ref["losses"]))),
        "grad_gap": worst_leaf_gap(prog["grad"], g, g)["gap"],
        "delta_gap": worst_leaf_gap(prog["change"][n_checked],
                                    ref["change"][n_checked], g)["gap"],
        "sync_gap": worst_leaf_gap(prog["change"][n_synced],
                                   ref["change"][n_synced], g)["gap"],
    }


CONTROLS = {"fp8": {"lowp": "fp8"}, "half_batch": {"batch_frac": 0.5},
            "no_exchange": {"exchange": False}}


def run(ctx) -> Dict:
    """One run of a training cell. `ctx` gives cfg, mix, seed, seconds,
    trace (bool), log, t_start, chips, the reference model module, and the
    hooks a test may set."""
    import jax
    from repro.telemetry import difference

    cfg, mix, log, model = ctx.cfg, ctx.mix, ctx.log, ctx.model
    mcfg = model_config(cfg, model)
    M, B, S = mix["num_workers"], mix["local_batch"], mix["seq_len"]
    n_checked, n_sync = int(mix["checked_steps"]), int(
        mix["checked_deliveries"])
    trainer = build_trainer(mcfg, mix, ctx.seed)
    rec = Recorder(trainer.segment_runner)
    trainer.segment_runner = rec
    proto = Protocol(trainer.engine)
    if ctx.break_program is not None:
        ctx.break_program(trainer)

    # -- the checked steps: through the window's own loop and feed ----------
    p0 = jax.tree.map(lambda a: a[0], trainer.params_stack)

    def change():
        return _leaf_norms(jax.tree.map(lambda a, b: a - b[None],
                                        trainer.params_stack, p0))
    drive(trainer, proto, 1)
    prog = {"grad": {k: v / (1 - B1) for k, v in
                     _leaf_norms(trainer.opt_state.mu).items()},
            "change": {n_checked: None}}
    drive(trainer, proto, n_checked)
    prog["change"][n_checked] = change()
    while proto.n_deliver < n_sync and \
            trainer.step < mix["warm_max_steps"]:
        segment(trainer, proto, mix["total_steps"])
    n_synced = trainer.step
    prog["change"][n_synced] = change()
    syncs = [s for s in proto.syncs if s[2] < n_synced]
    del p0
    losses = np.concatenate([np.asarray(x, np.float64) for x in rec.losses])
    tokens = np.concatenate([b["tokens"] for b in rec.batches])
    labels = np.concatenate([b["labels"] for b in rec.batches])
    rec.keep_batches, rec.batches = False, []
    prog["losses"] = losses[:n_synced].T
    batches = [[(tokens[t, m], labels[t, m]) for t in range(n_synced)]
               for m in range(M)]
    rows = tokens[:n_synced].reshape(-1, S)
    distinct_rows = len({r.tobytes() for r in rows})
    log(f"[train] checked steps {n_synced}; syncs (fragment, t_init, "
        f"t_deliver) {syncs}")

    # -- warm every program the window runs ---------------------------------
    K = mix["num_fragments"]
    while trainer.step < mix["warm_max_steps"] and (
            trainer.step < mix["warm_min_steps"]
            or len(proto.delivered) < K or len(proto.initiated) < K):
        segment(trainer, proto, mix["total_steps"])
    warm_steps = trainer.step
    log(f"[train] set-up: {warm_steps} steps, fragments initiated "
        f"{sorted(proto.initiated)} delivered {sorted(proto.delivered)}, "
        f"segment programs {rec.trace_count}")

    # -- the window -----------------------------------------------------------
    setup_s = time.perf_counter() - ctx.t_start
    n0, d0, syncs0 = proto.n_init, proto.n_deliver, trainer.engine.n_syncs
    rec.losses = []
    ctx.counter.arm()
    counters0 = trainer.telemetry.snapshot()
    with ctx.window() as win:
        t0 = time.perf_counter()
        lengths = []
        while time.perf_counter() - t0 < ctx.seconds:
            lengths.append(segment(trainer, proto, mix["total_steps"]))
        window_s = time.perf_counter() - t0
    telemetry = difference(trainer.telemetry.snapshot(), counters0)
    steps = sum(lengths)
    ctx.counter.disarm()
    win_losses = np.concatenate([np.asarray(x) for x in rec.losses])
    failed = int(np.sum(~np.all(np.isfinite(win_losses), axis=1)))
    n_init, n_deliver = proto.n_init - n0, proto.n_deliver - d0
    mem = ctx.memory_peak()
    stats = trainer.engine.stats()
    fpt = flops.train_flops_per_token(cfg, S, model)
    tok = steps * M * B * S
    log(f"[train] window: {steps} steps, {tok} tokens in {window_s:.4f} s; "
        f"{n_init} initiations, {n_deliver} deliveries, "
        f"n_syncs {trainer.engine.n_syncs} (+{trainer.engine.n_syncs - syncs0}"
        f"); segment lengths {dict(sorted(collections.Counter(lengths).items()))}")
    log(f"[train] programs lowered in the window {ctx.counter.lowered}, "
        f"compiled {ctx.counter.compiled} (count; should be 0)")
    log(f"[train] peak HBM {mem} B; model FLOPs/token {fpt:.6g}")
    log(f"[train] telemetry {json.dumps(telemetry)}")
    log(f"[train] simulated WAN (virtual clock, not measured): wall "
        f"{stats['wall_clock_s']:.1f} s, comm {stats['comm_seconds']:.1f} s, "
        f"overlap {stats['overlap_ratio']:.3f}")
    del trainer, rec, proto
    gc.collect()

    # -- the reference, once the window has closed and the state is freed ----
    t_ref = time.perf_counter()
    marks = (n_checked, n_synced)
    ref = reference_readings(model, cfg, mix, ctx.seed, batches, syncs,
                             marks)
    numbers = compare(prog, ref, n_checked, n_synced)
    for n in marks:
        w = worst_leaf_gap(prog["change"][n], ref["change"][n], ref["grad"])
        log(f"[train] change after {n} steps: worst leaf {w['leaf']}, leaves "
            f"left out {w['skipped']}")
    log(f"[train] reference ran in {time.perf_counter() - t_ref:.2f} s; "
        f"program losses {prog['losses'].tolist()} reference "
        f"{ref['losses'].tolist()}; distinct rows {distinct_rows}/"
        f"{len(rows)}")
    if distinct_rows != len(rows):
        numbers["repeated_rows"] = float(len(rows) - distinct_rows)
    if len(syncs) < n_sync:
        numbers["missing_deliveries"] = float(n_sync - len(syncs))
    control = None
    if ctx.control:
        control = {name: compare(reference_readings(
            model, cfg, mix, ctx.seed, batches, syncs, marks, **fault), ref,
            n_checked, n_synced) for name, fault in CONTROLS.items()}
    return {
        "control": control,
        "attempted": steps, "failed": failed, "numbers": numbers,
        "end_to_end": {"train_tokens_per_s": tok / window_s,
                       "setup_s": setup_s},
        "record": {"steps": steps, "tokens": tok, "window_s": window_s,
                   "flops_per_token": fpt, "initiations": n_init,
                   "deliveries": n_deliver, "warm_steps": warm_steps,
                   "lowered_in_window": ctx.counter.lowered,
                   "telemetry": telemetry},
        "memory_peak_bytes": mem,
        "window": win,
    }
