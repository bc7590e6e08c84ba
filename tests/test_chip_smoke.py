"""The on-chip entry points, as far as a machine with no chip can hold
them: chip_smoke.py and bench.py refuse to run without a TPU and print
no result; chip_smoke's explicit toy dry run passes end to end on the
CPU; the compile cache lives where JAX_COMPILATION_CACHE_DIR says, else
at one fixed path in the checkout."""

import json
import os
import pathlib
import subprocess
import sys

from ceph_tpu.common import envutil

REPO = pathlib.Path(__file__).resolve().parents[1]


def run_script(name, *args, env=None, timeout=300):
    e = dict(os.environ)
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("XLA_FLAGS", None)        # one CPU device, like a bare shell
    e.update(env or {})
    return subprocess.run([sys.executable, str(REPO / name), *args],
                          cwd=REPO, env=e, capture_output=True, text=True,
                          timeout=timeout)


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert envutil.compile_cache_dir() == str(REPO / ".jax_cache")
    assert envutil.compile_cache_dir() == envutil.compile_cache_dir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert envutil.compile_cache_dir() == "/some/dir"


def test_chip_smoke_without_tpu_exits_nonzero_and_prints_no_result():
    p = run_script("chip_smoke.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bench_without_tpu_exits_nonzero_and_prints_no_metric_row():
    p = run_script("bench.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_chip_smoke_cpu_dry_run_passes_and_caches_where_told(tmp_path):
    cache = tmp_path / "cache"
    p = run_script("chip_smoke.py", "--cpu-dry-run", "--seed", "3",
                   env={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    # every line but the verdict says what it ran on
    assert all("platform=cpu" in ln for ln in lines[:-1])
    # the last line is the verdict: exactly these keys, nothing else
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    # the line before it is the summary
    res = json.loads(lines[-2][lines[-2].index("{"):])
    assert res["device"] == verdict["device"]
    assert res["ok"] is True and res["dry_run"] is True
    assert res["device"]["platform"] == "cpu"
    assert res["claim"] is None
    assert all(res["phases"][ph]["ok"]
               for ph in ("kernel", "cluster", "degraded", "crush"))
    assert res["phases"]["cluster"]["device_byte_fraction"] == 1.0
    assert res["phases"]["cluster"]["after_write"]["host_bytes"] == 0
    assert res["phases"]["degraded"]["device_fallbacks"] == 0
    # the cache went where the environment said, and was written
    assert res["compile_cache_dir"] == str(cache)
    assert res["jax"]["cache_misses"] > 0
    assert any(cache.iterdir())
