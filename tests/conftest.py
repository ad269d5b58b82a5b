"""Test configuration.

Multi-chip sharding is tested on a virtual 8-device CPU mesh: JAX must see
these env vars before its first import, so they are set at conftest import
time (pytest imports conftest before test modules).
"""
import os
import sys

# Force, not setdefault: the tests are the CPU twin whatever the caller's
# environment names, and subprocesses spawned by tests inherit os.environ —
# a chip belongs to one process at a time, so a child left on the default
# backend would contend for it.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# `mesh` marker guard: the fleet-mesh suites need >= 8 devices (the virtual
# CPU mesh the env vars above request). An environment that cannot provide
# them — e.g. jax honoring a pre-set smaller XLA_FLAGS — SKIPS with a
# logged reason instead of failing on shard_state's divisibility assert.
# ---------------------------------------------------------------------------
MESH_TEST_DEVICES = 8
_mesh_probe_result = None


def _mesh_probe(want: int = MESH_TEST_DEVICES):
    """(ok, reason) — cached device-count probe for mesh-marked tests."""
    global _mesh_probe_result
    if _mesh_probe_result is not None:
        return _mesh_probe_result
    try:
        import jax as _jax
        n = len(_jax.devices())
        if n < want:
            _mesh_probe_result = (
                False, f"need {want} devices for the virtual fleet mesh, "
                       f"have {n}")
        else:
            _mesh_probe_result = (True, "")
    except Exception as e:  # noqa: BLE001 — any breakage means "skip"
        _mesh_probe_result = (False, f"jax devices unavailable: {e!r}")
    return _mesh_probe_result


# ---------------------------------------------------------------------------
# `multiproc` marker guard: the shared-deployment funnel suites (ISSUE 20)
# fork real worker/balancer processes. A single-core box (the fleet would
# just timeslice one CPU and time out) or an environment that cannot spawn
# the interpreter SKIPS with a logged reason instead of flaking.
# ---------------------------------------------------------------------------
MULTIPROC_MIN_CPUS = 2
_multiproc_probe_result = None


def _multiproc_probe():
    """(ok, reason) — cached cpu-count + spawn-capability probe for
    multiproc-marked tests."""
    global _multiproc_probe_result
    if _multiproc_probe_result is not None:
        return _multiproc_probe_result
    try:
        n = os.cpu_count() or 1
        if n < MULTIPROC_MIN_CPUS:
            _multiproc_probe_result = (
                False, f"need {MULTIPROC_MIN_CPUS} cpus for a real "
                       f"multi-process deployment, have {n}")
            return _multiproc_probe_result
        import subprocess
        proc = subprocess.run([sys.executable, "-c", "print('ok')"],
                              capture_output=True, text=True, timeout=60)
        if proc.returncode != 0 or "ok" not in proc.stdout:
            _multiproc_probe_result = (
                False, f"cannot spawn {sys.executable}: rc="
                       f"{proc.returncode}, stderr={proc.stderr[-200:]!r}")
            return _multiproc_probe_result
        _multiproc_probe_result = (True, "")
    except Exception as e:  # noqa: BLE001 — any breakage means "skip"
        _multiproc_probe_result = (False, f"process spawn broken: {e!r}")
    return _multiproc_probe_result


def pytest_collection_modifyitems(config, items):
    import pytest

    for marker, probe in (("mesh", _mesh_probe),
                          ("multiproc", _multiproc_probe)):
        if not any(marker in item.keywords for item in items):
            continue
        ok, reason = probe()
        if ok:
            continue
        print(f"# skipping {marker}-marked tests: {reason}", file=sys.stderr)
        skip = pytest.mark.skip(reason=f"{marker} unavailable: {reason}")
        for item in items:
            if marker in item.keywords:
                item.add_marker(skip)
