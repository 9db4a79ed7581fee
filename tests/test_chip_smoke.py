"""chip_smoke.py rehearsed without a chip, and the pieces it stands on:
seed-determined paper matrices and the fixed compile-cache location.

The on-chip run itself is ``python chip_smoke.py``; here the script runs
end to end on the CPU at a small n, with the
test steering its module constants (platform, size, kernel check)."""

import hashlib
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import numpy as np

from conftest import run_multidevice_script

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"

# sha256 of round(1e6 * synthesize("nemeth03", seed=0)) at the CPU size
NEMETH03_SEED0_SHA = ("b31ed38e0a05904233a52964b8b2f1b2"
                      "544dd4576284f36184a6e25e6726ecbe")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_paper_matrix_checksum_is_pinned():
    from repro.configs.svd_paper import synthesize

    a = synthesize("nemeth03", seed=0)
    q = np.round(a * 1e6).astype(np.int64)
    assert hashlib.sha256(q.tobytes()).hexdigest() == NEMETH03_SEED0_SHA


def test_paper_matrix_bit_identical_across_processes():
    code = ("import hashlib\n"
            "from repro.configs.svd_paper import synthesize\n"
            "a = synthesize('fv1', seed=1)\n"
            "print(hashlib.sha256(a.tobytes()).hexdigest())\n")
    digests = set()
    for hash_seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hash_seed,
                   PYTHONPATH=str(ROOT / "src"))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        digests.add(out.stdout.strip())
    assert len(digests) == 1, digests


def test_compile_cache_prefers_the_environment(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_smoke_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(SMOKE)], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no tpu" in out.stderr


def test_smoke_one_chip_rehearsal_on_cpu(monkeypatch, tmp_path, capsys):
    smoke = _load_smoke()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(smoke, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(smoke, "MATRIX_N", 256)
    # interpret-mode kernels compile to no tpu_custom_call off the chip
    monkeypatch.setattr(smoke, "KERNEL_BACKEND", None)
    monkeypatch.setattr(smoke, "SERVE_SHAPES",
                        ((96, 64), (64, 80), (120, 72)))
    assert smoke.main(["--seed", "0"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    for name in smoke.BACKENDS:
        assert any(line.startswith(f"[{name}] compile_s=")
                   for line in lines), name
    assert not any(line.endswith("FAIL") for line in lines)


def test_smoke_four_chip_rehearsal_on_virtual_devices(tmp_path):
    script = f"""
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", "chip_smoke.py")
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.REQUIRED_PLATFORM = "cpu"
smoke.MATRIX_N = 256
assert smoke.main(["--chips", "4", "--seed", "1"]) == 0
print("FOUR_OK")
"""
    run_multidevice_script(script, "FOUR_OK", devices=4)
