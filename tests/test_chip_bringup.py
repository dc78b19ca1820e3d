"""Bring-up invariants (ISSUE 21): where the compile cache lives, the one
peak-FLOP/s table, and ``chip_smoke.py``'s refusal to run without a TPU."""

import json
import os
import subprocess
import sys
import types

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_left_alone_when_env_places_it(monkeypatch, tmp_path):
    import jax

    from fedml_tpu.core import cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    assert cache.setup_persistent_cache() == str(tmp_path)
    # JAX reads the variable itself; the program sets no directory in code
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_fixed_in_checkout_when_env_unset(monkeypatch):
    import jax

    from fedml_tpu.core import cache

    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert cache.setup_persistent_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path from any process on any host: nothing about the machine
    # goes into the name (two fresh interpreters; cache_dir() needs no jax)
    env = {k: v for k, v in os.environ.items() if k != cache.ENV_VAR}
    code = "from fedml_tpu.core.cache import cache_dir; print(cache_dir())"
    seen = {subprocess.run([sys.executable, "-c", code], cwd=_REPO, env=env,
                           capture_output=True, text=True, timeout=60,
                           check=True).stdout.strip() for _ in range(2)}
    assert seen == {want}


def test_peak_table_known_unknown_and_cpu():
    import jax

    from fedml_tpu.ops import flops

    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert flops.device_peak_flops(dev("tpu", "TPU v5 lite")) == 197e12
    assert flops.device_peak_flops(dev("tpu", "TPU v4")) == 275e12
    assert flops.device_peak_flops(dev("tpu", "TPU v5")) == 459e12
    assert flops.device_peak_flops(dev("tpu", "TPU v6 lite")) == 918e12
    # a v5 kind the table does not know is an error — never the v5p's peak
    with pytest.raises(ValueError, match="TPU v5 ultra"):
        flops.device_peak_flops(dev("tpu", "TPU v5 ultra"))
    assert flops.device_peak_flops(dev("cpu", "cpu")) is None
    assert flops.device_peak_flops(jax.devices()[0]) is None
    assert flops.local_peak_flops() is None


def _smoke(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one device: the smoke sizes its own meshes
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=_REPO,
                          env=env, capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_refuses_without_a_tpu():
    res = _smoke(timeout=30)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert res.stdout.strip() == ""  # no leg ran, no result printed


def test_chip_smoke_dry_run_is_explicit_and_labelled():
    res = _smoke("--dry-run-cpu", timeout=600)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-4000:]
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "dry_run": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    for leg in ("A", "C", "D", "E", "B"):
        assert f"dry_run leg {leg} PASSED" in res.stdout
