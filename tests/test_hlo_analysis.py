"""HLO analyzer: trip-count-correct FLOPs/bytes/collective extraction."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import (HloModule, analyze, roofline_terms,
                                       top_contributors)

D, L = 128, 8


def _scan_fn(params, x):
    def body(c, p):
        return jax.nn.relu(c @ p), None
    out, _ = jax.lax.scan(body, x, params)
    return out.mean()


def _unrolled_fn(params, x):
    for i in range(L):
        x = jax.nn.relu(x @ params[i])
    return x.mean()


def _compile(fn):
    return jax.jit(fn).lower(
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
        jax.ShapeDtypeStruct((4, D), jnp.float32)).compile()


def test_scan_flops_match_unrolled():
    a_scan = analyze(_compile(_scan_fn).as_text())
    a_unroll = analyze(_compile(_unrolled_fn).as_text())
    assert a_scan["flops"] > 0
    ratio = a_scan["flops"] / a_unroll["flops"]
    # slicing ops are traffic-only (no fake elementwise flops), so the scan
    # variant counts slightly fewer non-dot flops than the unrolled one
    assert 0.85 < ratio < 1.15, ratio


def test_unrolled_matches_xla_cost_analysis():
    c = _compile(_unrolled_fn)
    ours = analyze(c.as_text())["flops"]
    xla = c.cost_analysis()["flops"]
    # elementwise ops are approximated at 1 flop/element; dots dominate
    assert abs(ours - xla) / xla < 0.15


def test_xla_undercounts_scan_but_we_dont():
    """Documents the bug this module exists to fix."""
    c = _compile(_scan_fn)
    xla = c.cost_analysis()["flops"]
    ours = analyze(c.as_text())["flops"]
    assert ours > 4 * xla  # XLA counts the 8-trip body once


def test_dot_flops_exact():
    def f(a, b):
        return a @ b
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((32, 48), jnp.float32),
                         jax.ShapeDtypeStruct((48, 16), jnp.float32)
                         ).compile()
    a = analyze(c.as_text())
    expect = 2 * 32 * 48 * 16
    assert abs(a["flops"] - expect) / expect < 0.05


def test_bytes_reasonable_for_copy():
    def f(x):
        return x * 2.0
    n = 1 << 16
    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n,), jnp.float32)).compile()
    a = analyze(c.as_text())
    # one read + one write of 256 KiB each
    assert n * 4 * 1.5 <= a["bytes"] <= n * 4 * 4


def test_roofline_terms_math():
    terms = roofline_terms({"flops": 197e12, "bytes": 0.0,
                            "collective_bytes": 0.0})
    assert abs(terms["t_compute"] - 1.0) < 1e-9
    assert terms["dominant"] == "compute"
    terms = roofline_terms({"flops": 0.0, "bytes": 819e9,
                            "collective_bytes": 100e9})
    # 100 GB over 50 GB/s = 2 s > 1 s of HBM time ⇒ collective-bound
    assert terms["dominant"] == "collective"
    assert abs(terms["t_collective"] - 2.0) < 1e-9
    terms = roofline_terms({"flops": 0.0, "bytes": 819e9,
                            "collective_bytes": 10e9})
    assert terms["dominant"] == "memory"


def test_top_contributors_nonempty():
    rows = top_contributors(_compile(_scan_fn).as_text(), 5, "bytes")
    assert rows and rows[0][0] > 0
