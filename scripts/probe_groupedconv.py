#!/usr/bin/env python
"""Microbenchmark the client-vmapped ResNet-20 conv regime on the real chip.

Small repeated jit calls with identical inputs mis-timed when host-clocked
(impossible >100% MFU observed), so every probe here runs its op in a
jitted lax.scan CHAIN of `reps` iterations whose input depends on the previous
output — the device must execute them sequentially, and one dispatch covers
the whole chain.  Per-op time = chain time / reps.

Times, for each ResNet-20 stage shape at n=64 clients x batch 128:
  conv_g    — grouped conv (feature_group_count=n): the vmapped-model form
  mm_eq     — batched matmul over im2col-SHAPED operands.  NOTE: this
              materializes the (M, 9*cin) patch matrix, i.e. 9x the input
              traffic of a direct conv, and uses square K=N=9*cin (chain
              shape stability) — a reference point for the im2col-matmul
              bandwidth regime, NOT a lane-equivalent conv ceiling.  The
              ceiling argument lives in PERF.md (trace rate + roofline).
  bn_relu   — conv_g + train-mode batch-norm + relu (the fused stage cost)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def chain_time(op, x0, reps=20):
    """Run x -> op(x) `reps` times inside one jitted scan; return s/op."""

    @jax.jit
    def chained(x):
        def body(c, _):
            return op(c), ()
        out, _ = jax.lax.scan(body, x, None, length=reps)
        return out

    out = chained(x0)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = chained(x0)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main():
    n, b = 64, 128
    stages = [(32, 32, 16, 16), (16, 16, 32, 32), (8, 8, 64, 64)]
    dev = jax.devices()[0]
    from fedml_tpu.ops import flops as flopslib

    peak = flopslib.device_peak_flops(dev)
    report = {"device": str(getattr(dev, "device_kind", dev.platform)),
              "n_clients": n, "batch": b, "peak_tflops": peak / 1e12}

    for (h, w, cin, cout) in stages:
        assert cin == cout
        key = jax.random.PRNGKey(0)
        xg = jax.random.normal(key, (b, h, w, n * cin), jnp.bfloat16)
        wg = jax.random.normal(key, (3, 3, cin, n * cout), jnp.bfloat16) * 0.05
        scale = jnp.ones((n * cout,), jnp.float32)
        bias = jnp.zeros((n * cout,), jnp.float32)

        def conv_only(x):
            y = jax.lax.conv_general_dilated(
                x, wg, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=n, preferred_element_type=jnp.bfloat16)
            # renormalize so the chain doesn't overflow; cost counted in all probes
            return y * jax.lax.rsqrt(jnp.float32(9 * cin)).astype(jnp.bfloat16)

        def conv_bn_relu(x):
            y = jax.lax.conv_general_dilated(
                x, wg, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=n, preferred_element_type=jnp.bfloat16)
            yf = y.astype(jnp.float32)
            mean = yf.mean(axis=(0, 1, 2), keepdims=True)
            var = yf.var(axis=(0, 1, 2), keepdims=True)
            out = (yf - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias
            return jax.nn.relu(out).astype(jnp.bfloat16)

        A = jax.random.normal(key, (n, b * h * w, 9 * cin), jnp.bfloat16) * 0.05
        Bm = jax.random.normal(key, (n, 9 * cin, 9 * cin), jnp.bfloat16) * 0.05

        def mm_eq(a):
            # square K=N=9*cin keeps the chain shape-stable; flops scaled below
            return jnp.einsum("nik,nko->nio", a, Bm,
                              preferred_element_type=jnp.bfloat16)

        fl_conv = 2 * 9 * cin * cout * h * w * b * n
        fl_mm = 2 * (b * h * w) * (9 * cin) * (9 * cin) * n
        t_g = chain_time(conv_only, xg)
        t_bn = chain_time(conv_bn_relu, xg)
        t_m = chain_time(mm_eq, A)
        report[f"s{h}x{w}x{cin}"] = {
            "conv_grouped_ms": t_g * 1e3, "conv_grouped_mfu": fl_conv / t_g / peak,
            "conv_bn_relu_ms": t_bn * 1e3, "bn_relu_overhead_ms": (t_bn - t_g) * 1e3,
            "mm_eq_ms": t_m * 1e3, "mm_eq_mfu": fl_mm / t_m / peak,
        }
    print("GROUPEDCONV " + json.dumps(report))


if __name__ == "__main__":
    main()
