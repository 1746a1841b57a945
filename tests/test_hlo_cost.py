"""Loop-aware HLO cost analyzer: exactness on known programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compat, hlo_cost, locality


def _text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


X = jax.ShapeDtypeStruct((128, 128), jnp.float32)


def test_direct_matmul_flops():
    def f(x, w):
        for _ in range(10):
            x = x @ w
        return x
    r = hlo_cost.analyze(_text(f, X, X))
    assert r["flops"] == pytest.approx(10 * 2 * 128 ** 3, rel=0.01)


def test_scan_trip_count_multiplies():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    r = hlo_cost.analyze(_text(f, X, X))
    assert r["flops"] == pytest.approx(10 * 2 * 128 ** 3, rel=0.01)


def test_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y
    r = hlo_cost.analyze(_text(f, X, X))
    assert r["flops"] == pytest.approx(20 * 2 * 128 ** 3, rel=0.01)


def test_builtin_cost_analysis_undercounts_loops():
    """Documents WHY hlo_cost exists: XLA's visitor counts bodies once."""
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y
    compiled = jax.jit(f).lower(X, X).compile()
    builtin = locality.extract_costs(compiled)["flops"]
    assert builtin < 0.2 * (10 * 2 * 128 ** 3)


def test_scan_bytes_linear_not_quadratic():
    """In-place DUS accounting: stacking N slices costs O(N), not O(N^2)."""
    def f(x):
        def body(c, _):
            c = c * 2.0
            return c, c
        _, ys = jax.lax.scan(body, x, None, length=64)
        return ys
    r = hlo_cost.analyze(_text(f, X))
    slice_bytes = 128 * 128 * 4
    # the per-step constant depends on how many copies/fusions this XLA
    # build emits around the DUS (observed 8.0-8.1x across versions); the
    # claim under test is linearity, so cap at a loose 16x per step —
    # quadratic stacking would be ~32x here (64 slices * avg half stack)
    # and grows with length, a constant factor does not
    assert r["bytes"] < 64 * slice_bytes * 16
    assert r["bytes"] >= 64 * slice_bytes        # at least writes the stack


def _sharded_text(n_dev, fn, arg_specs, in_specs, out_spec):
    import os
    mesh = compat.make_mesh((2, n_dev // 2), ("data", "model"))
    with jax.set_mesh(mesh):
        return jax.jit(fn, in_shardings=in_specs,
                       out_shardings=out_spec).lower(*arg_specs).compile().as_text()


def test_collective_accounting_smoke():
    """all-reduce of a known tensor size is counted with correct bytes."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (run under dryrun env)")


def test_locality_report_parsing():
    txt = """
ENTRY %main (p0: f32[16,16]) -> f32[16,16] {
  %p0 = f32[16,16]{1,0} parameter(0)
  ROOT %ar = f32[16,16]{1,0} all-reduce(%p0), channel_id=1, replica_groups=[2,4]<=[8], use_global_device_ids=true, to_apply=%add
}
"""
    rep = locality.analyze_hlo(txt)
    assert rep.count == 1
    assert rep.by_kind["all-reduce"].operand_bytes == 16 * 16 * 4
    # ring all-reduce: 2 (g-1)/g x operand
    assert rep.wire_bytes == pytest.approx(16 * 16 * 4 * 2 * 3 / 4)


def test_p_local_metric():
    rep = locality.LocalityReport(by_kind={
        "all-gather": locality.CollectiveStats(1, 100.0, 300.0)})
    assert rep.p_local(3000.0) == pytest.approx(0.9)
    assert rep.p_local(0.0) == 1.0
