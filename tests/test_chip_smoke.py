"""``chip_smoke.py`` without the chip: its entry point refuses the CPU,
and its phases, called at a tiny size with the kernels in interpret
mode, run end to end and pass their own checks."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.serve.executor import ExecutorConfig

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)


def test_entry_point_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok": true' not in proc.stdout


def test_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cs.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cs.cache_dir() == str(ROOT / ".jax_cache")


def test_phases_run_end_to_end_in_interpret_mode(monkeypatch, capsys):
    """The one-chip run at a tiny size: fp32 and int8 servers behind the
    front-end, upserts, oracle and re-rank checks, recall. The two
    device-only checks must refuse what the CPU compiles (interpreted
    kernels carry no ``tpu_custom_call``)."""
    refused = []
    real_path, real_kernels = cs.check_device_path, cs.check_kernels_compiled

    def device_path_on_cpu(server):
        assert server.executor.use_pallas
        with pytest.raises(cs.SmokeError, match="tpu_custom_call"):
            real_path(server)
        refused.append(server.precision)

    def kernels_on_cpu(*a, **kw):
        with pytest.raises(cs.SmokeError, match="not a Mosaic kernel"):
            real_kernels(*a, **kw)
        refused.append("kernels")

    monkeypatch.setattr(cs, "check_device_path", device_path_on_cpu)
    monkeypatch.setattr(cs, "check_kernels_compiled", kernels_on_cpu)
    cs.run_one_chip(n_rows=2048, nlist=16, nprobe=4, batch=8,
                    ecfg=ExecutorConfig(use_pallas=True, qb_buckets=(8,)),
                    recall_sample=16)
    assert refused == ["fp32", "int8", "kernels"]
    out = capsys.readouterr().out
    for line in ("build:", "serve[fp32]:", "exact[fp32]:", "serve[int8]:",
                 "rerank[int8]:", "recall:"):
        assert line in out
    assert '"ok"' not in out
