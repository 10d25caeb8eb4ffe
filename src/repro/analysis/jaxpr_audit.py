"""Jaxpr auditor: machine-checked invariants over the TRACED programs of the
jitted protocol plane.

The repo's hot-path guarantees are properties of lowered computations, not of
Python source — "the fused deliver is exactly two Pallas dispatches", "no host
callback ever rides inside a jitted transition", "buffers declared donated
really alias their outputs". This module walks the closed jaxprs of the engine
transitions (`initiate`/`deliver`/`diloco_round`, per-leaf and fused), the
segment scan, and the serve decode/prefill steps, and enforces the declarative
registry in `analysis/budgets.py`:

  * ``check_pallas_budget``     — exact ``pallas_call`` dispatch counts
  * ``check_banned_primitives`` — no host callbacks / debug prints / infeed
  * ``check_no_f64``            — no float64 widening inside jitted programs
  * ``check_donation``          — declared donations appear in the lowering
    (counted as ``tf.aliasing_output`` / ``jax.buffer_donor`` attributes; one
    per donated pytree leaf)

`iter_subjaxprs`/`count_pallas_calls` are THE canonical jaxpr walker (hoisted
from tests/test_outer_update.py — the test now imports from here).

Checks raise :class:`AuditError`; the ``audit_*`` drivers collect violations
into plain string lists so `python -m repro.analysis` can report everything at
once. Drivers import the engine/trainer/serve modules lazily — `analysis` is
imported BY `core.trainer` and `serve.engine` (for the retrace sentinel), so
eager imports here would cycle.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.analysis import budgets as budgets_lib

# the tiny dense config every audit traces against (mirrors the test fixture:
# small enough that a full budget-table sweep is CI-cheap, deep enough that a
# per-leaf-vs-fused dispatch regression is visible)
_TINY_KW = dict(name="audit-tiny", family="dense", n_layers=4, d_model=64,
                n_heads=2, n_kv_heads=1, d_ff=128, vocab=128,
                compute_dtype="float32")


class AuditError(AssertionError):
    """A traced program violates a declared budget/contract."""


# ---------------------------------------------------------------------------
# the canonical jaxpr walker
# ---------------------------------------------------------------------------


def iter_subjaxprs(val):
    """Yield every (sub)jaxpr reachable from an eqn-params value: ClosedJaxpr
    (`.jaxpr`), bare Jaxpr (`.eqns`), and tuples/lists of either."""
    if hasattr(val, "jaxpr"):                      # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):                     # Jaxpr
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from iter_subjaxprs(v)


def count_pallas_calls(jaxpr) -> int:
    """Total `pallas_call` eqns in `jaxpr`, recursing into every subjaxpr
    (pjit bodies, scan/while/cond branches, custom_vjp closures, ...)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in iter_subjaxprs(v):
                n += count_pallas_calls(sub)
    return n


def iter_eqns(jaxpr):
    """Depth-first over every eqn in `jaxpr` and all nested subjaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in iter_subjaxprs(v):
                yield from iter_eqns(sub)


# ---------------------------------------------------------------------------
# checks (raise AuditError)
# ---------------------------------------------------------------------------


def check_pallas_budget(jaxpr, expected: int, label: str) -> None:
    got = count_pallas_calls(jaxpr)
    if got != expected:
        raise AuditError(
            f"{label}: {got} pallas_call dispatches in the traced program, "
            f"budget declares exactly {expected} (analysis/budgets.py)")


def check_banned_primitives(jaxpr, label: str,
                            banned=budgets_lib.BANNED_PRIMITIVES) -> None:
    hits = sorted({e.primitive.name for e in iter_eqns(jaxpr)
                   if e.primitive.name in banned})
    if hits:
        raise AuditError(
            f"{label}: banned primitive(s) {hits} inside a jitted "
            f"protocol-plane program (host callbacks/debug prints stall the "
            f"device pipeline)")


def check_no_f64(jaxpr, label: str) -> None:
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            if dt is not None and dt == jnp.dtype("float64"):
                raise AuditError(
                    f"{label}: float64 value produced by `{eqn.primitive.name}`"
                    f" — the protocol plane is f32/bf16 only (f64 halves "
                    f"accelerator throughput and doubles wire bytes)")


def count_donation_annotations(lowered_text: str) -> int:
    """Donated-buffer annotations in StableHLO text: `tf.aliasing_output`
    (input aliases an output buffer) plus `jax.buffer_donor` (donated but
    matched to no output — still released). One per donated pytree leaf
    that survives into the lowered computation."""
    return (lowered_text.count("tf.aliasing_output")
            + lowered_text.count("jax.buffer_donor"))


def count_lowered_args(lowered_text: str) -> int:
    """Number of parameters of the lowered module's public entry function."""
    m = re.search(r"func\.func public @\w+\((.*?)\)(?: ->|\s*\{)",
                  lowered_text, re.S)
    if not m:
        return 0
    return len(re.findall(r"%arg\d+:", m.group(1)))


def check_donation(lowered_text: str, expected_leaves: int, label: str,
                   total_input_leaves: Optional[int] = None) -> None:
    """Every donated leaf must carry an aliasing annotation — up to the
    leaves jit legitimately removes from the computation (unused args are
    dropped, untouched inputs are forwarded straight to outputs; both lose
    their annotation). `total_input_leaves` (all args, donated or not)
    bounds that allowance: annotations must land in
    [expected - dropped, expected], and never 0 while leaves are declared."""
    got = count_donation_annotations(lowered_text)
    dropped = 0
    if total_input_leaves is not None:
        dropped = max(0, total_input_leaves - count_lowered_args(lowered_text))
    lo = max(min(1, expected_leaves), expected_leaves - dropped)
    if not (lo <= got <= expected_leaves):
        raise AuditError(
            f"{label}: {got} donated-buffer annotations in the lowered "
            f"computation, declared donation covers {expected_leaves} pytree "
            f"leaves ({dropped} inputs dropped/forwarded by jit) — the "
            f"donate_argnums wiring regressed or a donated buffer silently "
            f"stopped aliasing its output")


def _collect(errors: List[str], fn: Callable[[], None]) -> None:
    try:
        fn()
    except AuditError as e:
        errors.append(str(e))


# ---------------------------------------------------------------------------
# shared tiny fixtures (lazy model/engine imports)
# ---------------------------------------------------------------------------


def _tiny_model():
    from repro.configs.base import ModelConfig
    return ModelConfig(**_TINY_KW)


def _tiny_stack(mcfg, M: int = 2):
    from repro.models import api
    params = api.init_params(mcfg, jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (M,) + a.shape).copy(), params)


def _engine_setup(fused: bool):
    from repro.configs.base import CoCoDCConfig
    from repro.core.fragments import make_fragmenter
    mcfg = _tiny_model()
    stack = _tiny_stack(mcfg, M=2)
    ccfg = CoCoDCConfig(num_workers=2, local_steps=10, num_fragments=2,
                        overlap_depth=2, fused_updates=fused)
    shape = jax.eval_shape(lambda: jax.tree.map(lambda a: a[0], stack))
    frag = make_fragmenter(mcfg, shape, ccfg.num_fragments)
    return ccfg, frag, stack


def _trace_transition(fns, state, stack, transition: str):
    if transition == "initiate":
        fn = lambda st, s: fns.initiate(st, 3, s, 0)          # noqa: E731
    elif transition == "deliver":
        fn = lambda st, s: fns.deliver(st, 5, s, 0)           # noqa: E731
    elif transition == "diloco_round":
        fn = fns.diloco_round
    else:
        raise ValueError(f"unknown transition {transition!r}")
    return jax.make_jaxpr(fn)(state, stack).jaxpr


# ---------------------------------------------------------------------------
# audit drivers
# ---------------------------------------------------------------------------


def audit_engine(budgets: Optional[Dict] = None) -> List[str]:
    """Trace every budgeted engine transition and enforce dispatch counts,
    the banned-primitive list, and the no-f64 rule. Also the method-coverage
    contract: every registered sync method must declare at least one dispatch
    budget (ROADMAP item-1 authors: `register_dispatch_budget`)."""
    from repro.core import engine_state as es
    from repro.core.methods import registered_methods
    if budgets is None:
        budgets = budgets_lib.ENGINE_DISPATCH_BUDGETS
    errors: List[str] = []
    covered = {m for (m, _, _) in budgets}
    for m in registered_methods():
        if m not in covered:
            errors.append(
                f"engine: method {m!r} is registered but declares no "
                f"dispatch budget — add rows via analysis.budgets."
                f"register_dispatch_budget so its traced transitions are "
                f"audited")
    for (method, fused, impl_mode), budget in sorted(budgets.items()):
        ccfg, frag, stack = _engine_setup(fused)
        kw = ({"fused_impl": impl_mode} if fused
              else {"dc_impl": impl_mode})
        fns = es.make_engine_fns(method, ccfg, frag, use_jit=True, **kw)
        state = es.init_state(method, ccfg, stack, frag=frag)
        n_leaves = len(frag.flat._by_path[0])     # fragment 0's leaf count
        for transition, want in sorted(budget.items()):
            label = (f"engine[{method} fused={fused} impl={impl_mode}]"
                     f".{transition}")
            expected = n_leaves if want is budgets_lib.LEAVES else want
            jaxpr = _trace_transition(fns, state, stack, transition)
            _collect(errors,
                     lambda j=jaxpr, e=expected, l=label:
                     check_pallas_budget(j, e, l))
            _collect(errors,
                     lambda j=jaxpr, l=label: check_banned_primitives(j, l))
            _collect(errors, lambda j=jaxpr, l=label: check_no_f64(j, l))
    return errors


def _segment_fixture(*, donate=None, max_segment: int = 8,
                     attn_impl: str = "ref"):
    """A real SegmentRunner over the tiny dense model, its step built by
    the trainer's own `make_single_step` (loss + AdamW), sized for
    tracing."""
    from repro.core.trainer import SegmentRunner, make_single_step
    from repro.optim import adamw_init
    mcfg = _tiny_model()
    stack = _tiny_stack(mcfg, M=2)
    opt = jax.vmap(adamw_init)(stack)
    single_step = make_single_step(mcfg, weight_decay=0.1,
                                   attn_impl=attn_impl)
    runner = SegmentRunner(single_step, max_segment=max_segment,
                           donate=donate)
    batch_seg = {"tokens": jnp.zeros((4, 2, 2, 8), jnp.int32),
                 "labels": jnp.zeros((4, 2, 2, 8), jnp.int32)}
    lrs = jnp.full((4,), 1e-3, jnp.float32)
    return runner, stack, opt, batch_seg, lrs


def audit_segment() -> List[str]:
    """The fused inner-step scan, per attn_impl: exact Pallas dispatch
    counts (none on the reference path), callback-free, f64-free."""
    errors: List[str] = []
    for attn_impl, budget in sorted(
            budgets_lib.SEGMENT_SCAN_PALLAS_CALLS.items()):
        runner, stack, opt, batch_seg, lrs = _segment_fixture(
            attn_impl=attn_impl)
        jaxpr = jax.make_jaxpr(runner._fn.fn)(stack, opt, batch_seg,
                                              lrs).jaxpr
        label = f"trainer.segment_scan[attn_impl={attn_impl}]"
        _collect(errors, lambda j=jaxpr, b=budget, l=label:
                 check_pallas_budget(j, b, l))
        _collect(errors, lambda j=jaxpr, l=label:
                 check_banned_primitives(j, l))
        _collect(errors, lambda j=jaxpr, l=label: check_no_f64(j, l))
    return errors


def _serve_engine(attn_impl: str):
    from repro.models import api
    from repro.serve.engine import ServeEngine
    mcfg = _tiny_model()
    params = api.init_params(mcfg, jax.random.PRNGKey(0))
    return ServeEngine(mcfg, params, n_slots=2, cache_len=32, max_prompt=8,
                       prefill_chunk=4, attn_impl=attn_impl)


def audit_serve() -> List[str]:
    """Serve decode/prefill steps: dispatch budgets per attn_impl (flash
    decode is ONE kernel for the whole layer scan), no callbacks, no f64."""
    errors: List[str] = []
    for attn_impl, budget in sorted(
            budgets_lib.SERVE_DISPATCH_BUDGETS.items()):
        eng = _serve_engine(attn_impl)
        traced = {
            "decode": jax.make_jaxpr(eng._decode_fn.fn)(
                eng.params, eng.state).jaxpr,
            "prefill": jax.make_jaxpr(eng._prefill_fn.fn)(
                eng.params, eng.state, 0).jaxpr,
        }
        for step, want in sorted(budget.items()):
            label = f"serve[attn_impl={attn_impl}].{step}"
            jaxpr = traced[step]
            _collect(errors, lambda j=jaxpr, w=want, l=label:
                     check_pallas_budget(j, w, l))
            _collect(errors, lambda j=jaxpr, l=label:
                     check_banned_primitives(j, l))
            _collect(errors, lambda j=jaxpr, l=label: check_no_f64(j, l))
    return errors


def audit_donation() -> List[str]:
    """Donation verification: force `donate=True` (the accelerator wiring,
    backend-independent at lower time) and require one aliasing annotation
    per pytree leaf of every arg declared donated in ENGINE_DONATION /
    SegmentRunner.DONATE_ARGNUMS."""
    from repro.core import engine_state as es
    errors: List[str] = []
    for fused in (False, True):
        ccfg, frag, stack = _engine_setup(fused)
        fns = es.make_engine_fns("cocodc", ccfg, frag, use_jit=True,
                                 donate=True)
        state = es.init_state("cocodc", ccfg, stack, frag=frag)
        args = {"initiate": (state, 3, stack, 0),
                "deliver": (state, 5, stack, 0),
                "diloco_round": (state, stack)}
        for name, argnums in sorted(es.ENGINE_DONATION.items()):
            expected = sum(len(jax.tree.leaves(args[name][i]))
                           for i in argnums)
            # static args (the fragment id p) carry no leaves
            total = len(jax.tree.leaves(args[name][:3 if name !=
                                                   "diloco_round" else 2]))
            text = getattr(fns, name).lower(*args[name]).as_text()
            _collect(errors, lambda t=text, e=expected, n=total,
                     l=f"engine[cocodc fused={fused}].{name} donation":
                     check_donation(t, e, l, n))
    runner, stack, opt, batch_seg, lrs = _segment_fixture(donate=True)
    expected = len(jax.tree.leaves(stack)) + len(jax.tree.leaves(opt))
    total = len(jax.tree.leaves((stack, opt, batch_seg, lrs)))
    text = runner._fn.fn.lower(stack, opt, batch_seg, lrs).as_text()
    _collect(errors, lambda: check_donation(
        text, expected, "trainer.segment_scan donation", total))
    return errors
