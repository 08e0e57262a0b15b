"""What a CPU can check of the chip bring-up: where the compile cache
is placed, and that `chip_smoke.py` refuses to run without a TPU."""

import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = (
    "import jax, defer_tpu; print(jax.config.jax_compilation_cache_dir)"
)


def _cache_dir(tmp_path, env_value):
    """The cache directory a fresh process ends up with, started from
    `tmp_path` so nothing is derived from the working directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c", PROBE],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_placement(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it and the package sets
    nothing. Unset: `<checkout>/.jax_cache`, the same path from two
    processes started in different directories."""
    mine = str(tmp_path / "elsewhere")
    assert _cache_dir(tmp_path, mine) == mine
    other = tmp_path / "sub"
    other.mkdir()
    want = os.path.join(ROOT, ".jax_cache")
    assert _cache_dir(tmp_path, None) == want
    assert _cache_dir(other, None) == want


def test_default_smoke_refuses_a_cpu():
    """The default invocation stops at its platform check: a non-zero
    exit within seconds, no leg started, no result line."""
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no TPU" in out.stderr
    assert "leg" not in out.stdout and '"ok"' not in out.stdout


def test_result_line_holds_ok_and_device_only():
    """The reader of the smoke's last line checks its shape exactly:
    `ok` and `device`, the device as JAX reports it, no other key."""
    import json

    import jax

    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    line = chip_smoke.result_line(jax.devices())
    assert "\n" not in line
    got = json.loads(line)
    assert set(got) == {"ok", "device"} and got["ok"] is True
    dev = got["device"]
    assert set(dev) == {"platform", "kind", "count"}
    assert dev["platform"] == jax.devices()[0].platform
    assert dev["kind"] == jax.devices()[0].device_kind
    assert dev["count"] == len(jax.devices()) and type(dev["count"]) is int


@pytest.mark.slow
def test_smoke_debug_run_passes_on_the_cpu_mesh():
    """`--debug-cpu-tiny --chips 4` walks every leg at toy sizes on the
    8 virtual devices and prints no result line."""
    out = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "chip_smoke.py"),
            "--debug-cpu-tiny", "--chips", "4",
        ],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "debug run passed" in out.stdout
    assert '"ok"' not in out.stdout
