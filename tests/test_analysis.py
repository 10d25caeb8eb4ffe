"""Static-analysis tests: every checker class is proven SHARP on a seeded
violation (wrong dispatch count, impure ref.py, missing impl="auto",
unaligned BlockSpec, banned primitive, broken donation, f64 widening,
retrace churn), and the repo head is pinned clean against the full budget
registry (4 methods x fused on/off, serve, segment scan, donation)."""
import re
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import budgets
from repro.analysis.jaxpr_audit import (AuditError, audit_donation,
                                        audit_engine, audit_segment,
                                        audit_serve, check_banned_primitives,
                                        check_donation, check_no_f64,
                                        check_pallas_budget,
                                        count_donation_annotations,
                                        count_lowered_args,
                                        count_pallas_calls)
from repro.analysis.kernel_lint import (_lint_blockspecs, _lint_ops_contract,
                                        _lint_ref_purity, lint_kernel_family,
                                        lint_purity, run_kernel_lint)
from repro.analysis.retrace import RetraceError, RetraceSentinel


# ---------------------------------------------------------------------------
# jaxpr checks: seeded violations
# ---------------------------------------------------------------------------


def test_pallas_budget_trips_on_wrong_count():
    jaxpr = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((4,))).jaxpr
    assert count_pallas_calls(jaxpr) == 0
    with pytest.raises(AuditError, match="budget declares exactly 1"):
        check_pallas_budget(jaxpr, 1, "fixture")


def test_pallas_budget_counts_real_kernel():
    from repro.kernels.rms_norm.ops import rms_norm
    x = jnp.ones((2, 8, 64))
    w = jnp.ones((64,))
    jaxpr = jax.make_jaxpr(
        lambda x, w: rms_norm(x, w, impl="pallas"))(x, w).jaxpr
    check_pallas_budget(jaxpr, 1, "rms_norm pallas")         # passes
    with pytest.raises(AuditError):
        check_pallas_budget(jaxpr, 0, "rms_norm pallas")
    # and the ref dial stays kernel-free
    jaxpr_ref = jax.make_jaxpr(
        lambda x, w: rms_norm(x, w, impl="ref"))(x, w).jaxpr
    check_pallas_budget(jaxpr_ref, 0, "rms_norm ref")


def test_banned_primitive_trips_on_debug_print():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x + 1.0
    jaxpr = jax.make_jaxpr(f)(jnp.ones((3,))).jaxpr
    with pytest.raises(AuditError, match="debug_print"):
        check_banned_primitives(jaxpr, "fixture")
    # a clean program passes
    check_banned_primitives(
        jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones((3,))).jaxpr, "fixture")


def test_f64_check_trips_under_x64():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones((3,))).jaxpr
        with pytest.raises(AuditError, match="float64"):
            check_no_f64(jaxpr, "fixture")
    check_no_f64(jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((3,))).jaxpr,
                 "fixture")


def test_donation_check_trips_without_donate_argnums():
    text = jax.jit(lambda x: x + 1.0).lower(jnp.ones((4,))).as_text()
    assert count_donation_annotations(text) == 0
    with pytest.raises(AuditError, match="donated-buffer annotations"):
        check_donation(text, 1, "fixture", total_input_leaves=1)


def test_donation_check_passes_when_wired():
    text = jax.jit(lambda x: x + 1.0,
                   donate_argnums=(0,)).lower(jnp.ones((4,))).as_text()
    assert count_donation_annotations(text) == 1
    check_donation(text, 1, "fixture", total_input_leaves=1)


def test_count_lowered_args_reads_main_only():
    # %arg numbering restarts inside private helper functions — the public
    # entry signature is the only one that bounds jit's dropped-arg count
    text = textwrap.dedent("""\
        module @jit_f {
          func.func public @main(%arg0: tensor<4xf32>, %arg1: tensor<4xf32>)
              -> (tensor<4xf32>) {
            %0 = call @helper(%arg0, %arg1, %arg1) : ...
            return %0 : tensor<4xf32>
          }
          func.func private @helper(%arg0: tensor<4xf32>,
              %arg1: tensor<4xf32>, %arg2: tensor<4xf32>) -> tensor<4xf32> {
          }
        }
    """)
    assert count_lowered_args(text) == 2


# ---------------------------------------------------------------------------
# kernel-contract linter: seeded violations
# ---------------------------------------------------------------------------


def _write(tmp_path, name, src):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return p


def test_ref_purity_trips_on_pallas_import(tmp_path):
    p = _write(tmp_path, "ref.py", """\
        from jax.experimental import pallas as pl
        import jax.numpy as jnp

        def oracle(x):
            return pl.load(x, ())
    """)
    out = _lint_ref_purity(p)
    assert any("pure jnp" in v for v in out)


def test_ops_contract_trips_without_impl_dial(tmp_path):
    p = _write(tmp_path, "ops.py", """\
        import jax.numpy as jnp

        def my_op(x, *, block=128):
            return x * 2
    """)
    out = _lint_ops_contract(p)
    assert any('impl="auto"' in v for v in out)
    assert any("is_cpu" in v for v in out)
    assert any("ref oracle" in v for v in out)


def test_ops_contract_passes_on_conforming_module(tmp_path):
    p = _write(tmp_path, "ops.py", """\
        from repro.kernels import is_cpu
        from repro.kernels.fake.ref import my_op_ref

        def my_op(x, *, impl: str = "auto"):
            if impl == "ref":
                return my_op_ref(x)
            interpret = is_cpu()
            return x
    """)
    assert _lint_ops_contract(p) == []


def test_blockspec_lint_trips_on_unaligned_last_dim(tmp_path):
    p = _write(tmp_path, "kern.py", """\
        from jax.experimental import pallas as pl

        def build(x):
            return pl.BlockSpec((8, 100), lambda i: (i, 0))
    """)
    out = _lint_blockspecs(p, budgets.KernelContract())
    assert any("not lane-aligned" in v for v in out)


def test_blockspec_lint_trips_on_undeclared_dim(tmp_path):
    p = _write(tmp_path, "kern.py", """\
        from jax.experimental import pallas as pl

        def build(x, bq):
            return pl.BlockSpec((bq, 128), lambda i: (i, 0))
    """)
    out = _lint_blockspecs(p, budgets.KernelContract())
    assert any("not statically resolvable" in v for v in out)
    # declaring the bound resolves it
    ok = _lint_blockspecs(p, budgets.KernelContract(dim_bounds={"bq": 128}))
    assert ok == []


def test_blockspec_lint_trips_on_vmem_blowout(tmp_path):
    p = _write(tmp_path, "kern.py", """\
        from jax.experimental import pallas as pl

        def build(x):
            return pl.BlockSpec((4096, 1024), lambda i: (i, 0))
    """)
    out = _lint_blockspecs(p, budgets.KernelContract())   # 16 MiB > 8 MiB
    assert any("VMEM footprint" in v for v in out)


def test_family_lint_end_to_end(tmp_path):
    fam = tmp_path / "famx"
    fam.mkdir()
    (fam / "__init__.py").write_text("")
    _write(fam, "ref.py", """\
        import jax.experimental.pallas as pl

        def famx_ref(x):
            return x
    """)
    # no ops.py at all
    out = lint_kernel_family(fam, budgets.KernelContract())
    assert any("pure jnp" in v for v in out)
    assert any("missing ops.py" in v for v in out)


# ---------------------------------------------------------------------------
# repo head pinned clean against the full registry
# ---------------------------------------------------------------------------


def test_kernel_lint_clean_on_repo_head():
    assert run_kernel_lint() == []


def test_purity_lint_clean_on_repo_head():
    assert lint_purity() == []


def test_engine_dispatch_budgets_hold():
    """The full table: 4 methods x fused on/off x impl modes, each traced
    transition at its exact pallas_call budget, callback- and f64-free."""
    assert audit_engine() == []


def test_engine_audit_flags_unbudgeted_method():
    only = {("local", False, "ref"): {"diloco_round": 0}}
    errors = audit_engine(budgets=only)
    assert any("declares no dispatch budget" in e and "cocodc" in e
               for e in errors)


def test_register_dispatch_budget_validates_and_registers():
    with pytest.raises(ValueError, match="unknown transition"):
        budgets.register_dispatch_budget(
            "tmpm", fused=False, impl="ref", budget={"teleport": 0})
    key = ("tmpm", False, "ref")
    try:
        budgets.register_dispatch_budget(
            "tmpm", fused=False, impl="ref", budget={"deliver": 0})
        assert budgets.ENGINE_DISPATCH_BUDGETS[key] == {"deliver": 0}
        assert "tmpm" in budgets.budgeted_methods()
    finally:
        budgets.ENGINE_DISPATCH_BUDGETS.pop(key, None)


def test_segment_scan_audit_clean():
    assert audit_segment() == []


def test_serve_audit_clean():
    assert audit_serve() == []


def test_donation_audit_clean():
    assert audit_donation() == []


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------


def test_retrace_sentinel_trips_on_shape_churn():
    f = RetraceSentinel(jax.jit(lambda x: x * 2.0), name="fixture")
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))                      # same shape: no new trace
    assert f.trace_count == 1
    with pytest.raises(RetraceError, match="fixture"):
        f(jnp.ones((3,)))                  # second trace > budget of 1


def test_retrace_sentinel_rejects_plain_functions():
    with pytest.raises(TypeError, match="_cache_size"):
        RetraceSentinel(lambda x: x, name="fixture")


def test_segment_runner_trace_budget_is_log2():
    from repro.core.trainer import SegmentRunner
    runner = SegmentRunner(lambda p, o, b, lr: (p, o, 0.0), max_segment=64)
    assert runner._fn.max_traces == 7      # 64.bit_length(): chunks 64..1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_section_runs_clean(capsys):
    from repro.analysis.__main__ import main
    assert main(["--section", "kernel-contracts", "--section",
                 "purity"]) == 0
    out = capsys.readouterr().out
    assert "PASS" in out and "FAIL" not in out
