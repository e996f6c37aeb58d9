"""``chip_smoke.py`` refuses to run anywhere but on a TPU, the compile
cache it turns on lives where ``JAX_COMPILATION_CACHE_DIR`` says, and chip
peaks exist only for the device kinds they were published for."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.common import compile_cache
from repro.launch.mesh import chip_peaks

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Dev:
    def __init__(self, platform, kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, kind


def test_device_check_refuses_cpu():
    cs = _chip_smoke()
    with pytest.raises(SystemExit, match="needs a TPU"):
        cs.check_device(jax.devices("cpu"), 1)


def test_device_check_counts_chips():
    cs = _chip_smoke()
    assert cs.check_device([_Dev("tpu")], 1) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }
    with pytest.raises(SystemExit, match="--chips 4 needs 4 TPU devices"):
        cs.check_device([_Dev("tpu")], 4)


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _final_line_printed(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except json.JSONDecodeError:
        return False


@pytest.mark.parametrize("route,ok", [("kernel", True), ("mask", False),
                                      ("none", True), ("einsum", False)])
def test_route_check_takes_the_top_k_mask(route, ok):
    """A phase passes its route check with the codec on a kernel (top-k's
    selection or qsgd's pack) or off, and fails it on the XLA path or on
    the kernel-less ``mask`` route the top-k codec no longer reports."""
    line = {"routes": {"sgd": "fused_ragged", "agg": "kernel",
                       "defense": "kernel", "compress": route},
            "kernels_in_program": 2}
    assert _chip_smoke()._all_kernel(line) is ok


def test_script_exits_nonzero_on_cpu():
    proc = _run_script(ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not _final_line_printed(proc)


def test_script_exits_nonzero_outside_the_repository(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run_script(tmp_path)
    assert proc.returncode != 0
    assert not _final_line_printed(proc)


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_honors_env(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert compile_cache.enable_compile_cache() == path


def test_chip_peaks_keyed_by_device_kind():
    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("TPU v9 imaginary")
