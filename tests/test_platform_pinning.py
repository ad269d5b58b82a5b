"""Process boot and device selection (utils/config.py): where the compile
cache lives, when the device path may run on the CPU, and what never
borrows CPU devices. All on the CPU twin — these test the RULES, not
libtpu; `chip_smoke.py` is the proof on the chip."""
import os
import subprocess
import sys

import pytest

from openwhisk_tpu.utils.config import (JAX_CACHE_DIR, DeviceError,
                                        check_device_platform, cpu_requested)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, cwd=REPO, **env_overrides):
    """A fresh interpreter; an override of None removes the variable."""
    env = dict(os.environ, PYTHONPATH=REPO)
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-800:]
    return out.stdout.strip().splitlines()


def test_fresh_cpu_process_resolves_cpu_without_any_helper():
    """A fresh process with JAX_PLATFORMS=cpu resolves the CPU backend by
    itself — JAX honors the variable, no boot hook is involved."""
    lines = _run("import jax\nprint(jax.default_backend())\n",
                 JAX_PLATFORMS="cpu")
    assert lines[-1] == "cpu"


_BOOT = ("from openwhisk_tpu.utils.config import boot_jax\n"
         "boot_jax()\n"
         "import jax\n"
         "print(jax.config.jax_compilation_cache_dir)\n"
         "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n"
         "print(jax.config.jax_persistent_cache_min_entry_size_bytes)\n"
         "from jax._src import xla_bridge\n"
         "print(len(xla_bridge._backends))\n")


class TestCompileCache:
    def test_env_set_means_no_directory_set_in_code(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR is JAX's own variable: with it set
        the program sets no directory, and the cache is there."""
        lines = _run(_BOOT, JAX_PLATFORMS=None,
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        assert lines[0] == str(tmp_path)
        assert float(lines[1]) == 0.0 and int(lines[2]) == -1
        assert lines[3] == "0", "boot_jax must not initialize a backend"

    def test_unset_means_one_fixed_in_checkout_path(self, tmp_path):
        """Two processes in two working directories agree on the path, and
        it is inside the checkout: the directory is part of the cache key,
        so a path that moved with the cwd would never hit."""
        a = _run(_BOOT, cwd=REPO, JAX_PLATFORMS=None,
                 JAX_COMPILATION_CACHE_DIR=None)
        b = _run(_BOOT, cwd=str(tmp_path), JAX_PLATFORMS=None,
                 JAX_COMPILATION_CACHE_DIR=None)
        assert a[0] == b[0] == JAX_CACHE_DIR == os.path.join(REPO,
                                                             ".jax_cache")
        assert float(a[1]) == 0.0 and int(a[2]) == -1

    def test_cpu_twin_is_left_alone(self):
        lines = _run(_BOOT, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=None)
        assert lines[0] == "None"


class TestDeviceRule:
    """CPU only when JAX_PLATFORMS names it: JAX's own no-accelerator
    fallback (platform cpu, variable unset) is an error, not a quiet run."""

    def test_tpu_always_passes(self):
        check_device_platform("tpu", None)
        check_device_platform("tpu", "tpu")

    @pytest.mark.parametrize("named", ["cpu", "CPU", "cpu,tpu", " cpu "])
    def test_cpu_passes_only_when_named(self, named):
        assert cpu_requested(named)
        check_device_platform("cpu", named)

    @pytest.mark.parametrize("env", [None, "", "tpu", "cuda", "tpu,cpu"])
    def test_cpu_fallback_is_refused(self, env):
        # "tpu,cpu" is what a TPU host exports: the TPU is the default
        # backend there and the CPU merely a second platform
        assert not cpu_requested(env)
        with pytest.raises(DeviceError, match="needs a TPU"):
            check_device_platform("cpu", env)

    def test_other_accelerators_are_refused(self):
        with pytest.raises(DeviceError):
            check_device_platform("gpu", "cpu")

    def test_balancer_refuses_a_cpu_it_was_not_given(self, monkeypatch):
        """TpuBalancer.__init__ applies the rule before it builds any
        device state; standalone/controller `--balancer tpu` boot through
        it."""
        from openwhisk_tpu.controller.loadbalancer import TpuBalancer
        from openwhisk_tpu.core.entity import ControllerInstanceId
        from openwhisk_tpu.messaging import MemoryMessagingProvider
        monkeypatch.delenv("JAX_PLATFORMS")
        with pytest.raises(DeviceError, match="JAX_PLATFORMS=cpu"):
            TpuBalancer(MemoryMessagingProvider(), ControllerInstanceId("0"))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        bal = TpuBalancer(MemoryMessagingProvider(),
                          ControllerInstanceId("0"))
        assert bal.device["platform"] == "cpu"
        assert bal.kernel_profile()["device"] == bal.device


class TestWhoTouchesTheDevice:
    """A chip belongs to one process at a time: only the process that
    builds the device balancer may initialize a JAX backend."""

    def test_front_end_worker_never_initializes_a_backend(self):
        """The funnel front end (`controller --role frontend`, loadgen
        `--funnel` workers) forwards placement over the bus: building it
        must leave JAX's backend registry empty."""
        lines = _run(
            "from openwhisk_tpu.controller.loadbalancer.funnel import "
            "FunnelBalancer\n"
            "from openwhisk_tpu.core.entity import ControllerInstanceId\n"
            "from openwhisk_tpu.messaging import MemoryMessagingProvider\n"
            "FunnelBalancer(MemoryMessagingProvider(), "
            "ControllerInstanceId('100'), target=0)\n"
            "import sys\n"
            "from jax._src import xla_bridge\n"
            "print('jax' in sys.modules, len(xla_bridge._backends))\n")
        assert lines[-1].split()[-1] == "0"

    def test_device_server_refuses_to_boot_on_jaxs_quiet_cpu_fallback(self):
        """`standalone --balancer tpu` with no accelerator and no
        JAX_PLATFORMS=cpu: one clear error line, non-zero exit, nothing
        served. (JAX_PLATFORMS=tpu,cpu would make JAX itself raise; the
        empty value is the quiet fallback this rule exists for.)"""
        # an unloadable libtpu makes "no accelerator" true on any host
        env = dict(os.environ, PYTHONPATH=REPO,
                   TPU_LIBRARY_PATH="/nonexistent/libtpu.so")
        env.pop("JAX_PLATFORMS")
        out = subprocess.run(
            [sys.executable, "-m", "openwhisk_tpu.standalone", "--balancer",
             "tpu", "--port", "13998", "--no-ui"], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 1
        assert out.stderr.strip().splitlines()[-1].startswith(
            "error: the device balancer needs a TPU")
        assert "listening on" not in out.stdout


class TestMeshNeverBorrowsDevices:
    def test_make_mesh_raises_past_the_default_backend(self):
        import jax

        from openwhisk_tpu.parallel import make_fleet_mesh, make_mesh
        have = len(jax.devices())
        assert make_mesh(have).devices.size == have
        with pytest.raises(ValueError, match="backend has"):
            make_mesh(have + 1)
        with pytest.raises(ValueError, match="backend has"):
            make_fleet_mesh(2 * have)  # pow2, so only the count is wrong


class TestDeployChipAssignment:
    OWNERS = ["controller0", "controller1"]

    def test_more_owners_than_chips_is_refused_up_front(self):
        from openwhisk_tpu.tools import deploy
        with pytest.raises(SystemExit, match="2 device-owning services"):
            deploy.chip_env(self.OWNERS, {}, count_chips=lambda env: 1)

    def test_each_owner_gets_its_own_chip(self):
        from openwhisk_tpu.tools import deploy
        env = deploy.chip_env(self.OWNERS, {}, count_chips=lambda env: 4)
        assert [env[o]["TPU_VISIBLE_CHIPS"] for o in self.OWNERS] == ["0",
                                                                       "1"]
        assert len({env[o]["TPU_MESH_CONTROLLER_PORT"]
                    for o in self.OWNERS}) == 2
        # a single owner keeps every chip (the fleet mesh spans them)
        assert deploy.chip_env(self.OWNERS[:1], {},
                               count_chips=lambda env: 4) == {}

    def test_cpu_twin_hands_out_no_chips(self):
        from openwhisk_tpu.tools import deploy

        def never(env):
            raise AssertionError("the CPU twin must not count chips")
        assert deploy.chip_env(self.OWNERS, {"JAX_PLATFORMS": "cpu"},
                               count_chips=never) == {}

    def test_owners_follow_the_inventory(self):
        from openwhisk_tpu.tools import deploy
        inv = deploy.load_inventory(None)
        inv["controllers"].update(count=2, balancer="tpu")
        assert deploy.device_owners(inv) == self.OWNERS
        inv["controllers"]["balancer"] = "sharding"
        assert deploy.device_owners(inv) == []


def test_chip_smoke_refuses_the_cpu_twin_quickly():
    """`JAX_PLATFORMS=cpu python chip_smoke.py` exits non-zero at the first
    platform check, before it starts any leg, and prints no result."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "CPU twin" in out.stderr
