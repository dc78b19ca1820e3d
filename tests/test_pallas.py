"""Pallas kernel tests (interpret mode — CPU CI; the compiled path is
exercised on the real chip by the round driver's bench/verify runs)."""

import jax
import jax.numpy as jnp
import numpy as np


def test_quantize_kernel_matches_reference(eight_devices):
    from fedml_tpu.ops.pallas import (
        dequantize_int8,
        quantize_int8_reference,
        quantize_int8_stochastic,
    )

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (5000,)) * 3.0
    v, s, n = quantize_int8_stochastic(x, k, interpret=True)
    vr, sr, nr = quantize_int8_reference(x, k)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    assert n == nr == 5000
    assert v.dtype == jnp.int8

    back = dequantize_int8(v, s, n, interpret=True)
    assert back.shape == x.shape
    # error bounded by one quantization step per block
    assert float(jnp.abs(back - x).max()) <= float(s.max()) + 1e-6


def test_quantize_kernel_unbiased(eight_devices):
    from fedml_tpu.ops.compression import qsgd_int8_fused

    k = jax.random.PRNGKey(1)
    x = jax.random.normal(k, (2048,))
    est = jnp.stack([
        qsgd_int8_fused(x, jax.random.PRNGKey(i), interpret=True) for i in range(40)
    ]).mean(0)
    assert float(jnp.abs(est - x).mean()) < 0.01


def test_quantize_kernel_edge_shapes(eight_devices):
    from fedml_tpu.ops.pallas import dequantize_int8, quantize_int8_stochastic

    k = jax.random.PRNGKey(2)
    for n in (1, 1023, 1024, 1025, 4096):
        x = jax.random.normal(k, (n,))
        v, s, length = quantize_int8_stochastic(x, k, interpret=True)
        back = dequantize_int8(v, s, length, interpret=True)
        assert back.shape == (n,)
        assert float(jnp.abs(back - x).max()) <= float(s.max()) + 1e-6


def test_pallas_kernel_seconds_histogram(eight_devices):
    """Eager kernel invocations land in the process-global
    ``fedml_pallas_kernel_seconds`` histogram (labels=kernel) and surface in
    both the Prometheus rendering and the bench summary helper."""
    from fedml_tpu.obs.registry import REGISTRY
    from fedml_tpu.ops.pallas import kernel_time_summary, quantize_int8_stochastic

    hist = REGISTRY.get("fedml_pallas_kernel_seconds")
    assert hist is not None
    before = hist.count(kernel="quantize_int8_stochastic")
    x, key = jnp.ones(2048), jax.random.PRNGKey(0)
    quantize_int8_stochastic(x, key, interpret=True)  # eager -> observed
    assert hist.count(kernel="quantize_int8_stochastic") == before + 1
    summary = kernel_time_summary()
    assert summary["quantize_int8_stochastic"]["count"] >= 1
    assert "fedml_pallas_kernel_seconds_bucket" in REGISTRY.render()
    # traced invocations are NOT host-timed (wall clock there measures
    # tracing, not the kernel)
    n = hist.count(kernel="quantize_int8_stochastic")
    jax.jit(lambda xx: quantize_int8_stochastic(xx, key, interpret=True)[:2])(x)
    assert hist.count(kernel="quantize_int8_stochastic") == n


def test_pallas_kernel_sink_and_report_section(eight_devices):
    """Registered timing sinks see each eager observation (the cross-silo
    client ships them as metric records), and ``obs report`` renders those
    records as a per-kernel summary table."""
    from fedml_tpu.obs import report as obs_report
    from fedml_tpu.ops.pallas import quantize_int8_stochastic, timing

    records = []
    sink = timing.add_sink(lambda k, s: records.append(
        {"kind": "metric", "metric": "pallas_kernel_seconds", "kernel": k, "value": s}))
    try:
        quantize_int8_stochastic(jnp.ones(2048), jax.random.PRNGKey(0), interpret=True)
    finally:
        timing.remove_sink(sink)
    assert records and records[0]["kernel"] == "quantize_int8_stochastic"
    stats = obs_report.pallas_kernel_stats(records)
    assert stats[0]["kernel"] == "quantize_int8_stochastic" and stats[0]["n"] == len(records)
    trail = records + [{"kind": "span", "name": "round", "trace_id": "t",
                        "span_id": "s1", "round_idx": 0, "ts": 1.0, "dur_s": 1.0}]
    text = obs_report.render_report(trail)
    assert "pallas kernels" in text and "quantize_int8_stochastic" in text
    # a trail with no kernel records renders no (empty) kernel section
    assert "pallas kernels" not in obs_report.render_report(trail[-1:])
