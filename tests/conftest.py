"""Test harness: run everything on an 8-device CPU-emulated mesh.

The reference has no tests at all (SURVEY.md §4); multi-node behavior
was only ever exercised on physical hosts at hard-coded IPs (reference
src/test.py:20). Here CI needs no hardware: XLA's host platform is
forced to expose 8 virtual devices, so partitioning, device-pinned
pipelines, and shard_map collectives all run for real.

Must run before the first `import jax` anywhere in the test process.
"""

import os

# Force CPU even when the environment pre-selects a TPU platform (the
# benchmark harness uses the real chip; tests never should).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# Much of the suite's time is XLA:CPU compiling toy programs, and nobody
# deploys that code: compile it unoptimised. What the tests check — the
# traced programs and their results — is unchanged. Compile-heavy files
# ran about 29% faster with this flag (test_paged + test_spec_compose
# 102 s -> 72 s, test_train + test_api + test_checkpoint 176 s -> 125 s;
# PR 21, 8 cores).
if "xla_backend_optimization_level" not in _flags:
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

# A pytest plug-in may have imported jax before this file ran, which
# makes the env var too late — set the live config as well, before any
# backend initializes.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def trace_sanitizer():
    """The analysis subsystem's no-retrace guard
    (defer_tpu/analysis/sanitizer.py): wrap a warmed hot loop and the
    test fails with RetraceError if any watched jitted callable
    compiles a new variant inside the block."""
    from defer_tpu.analysis.sanitizer import trace_sanitizer as ts

    return ts


FLAKY = {"failures": 0}


def register_flaky_op() -> None:
    """Idempotently register the 'flaky' fault-injection op: raises
    while FLAKY['failures'] > 0 (decrementing), else identity. Shared
    by the elastic-recovery tests so both exercise the same fault."""
    from defer_tpu.ops.registry import op_names, register_op

    if "flaky" in op_names():
        return

    @register_op("flaky")
    def flaky_apply(params, inputs, attrs):
        if FLAKY["failures"] > 0:
            FLAKY["failures"] -= 1
            raise RuntimeError("transient stage failure")
        return inputs[0]


def write_keras_h5(path: str, weights: dict) -> None:
    """Write `{layer: [arrays]}` in the classic Keras save_weights h5
    layout (layer_names/weight_names attrs) for transplant tests."""
    import h5py

    with h5py.File(path, "w") as f:
        f.attrs["layer_names"] = [n.encode() for n in weights]
        for lname, arrays in weights.items():
            g = f.create_group(lname)
            wnames = [f"{lname}/w{i}".encode() for i in range(len(arrays))]
            g.attrs["weight_names"] = wnames
            for wn, a in zip(wnames, arrays):
                g.create_dataset(wn.decode(), data=a)
