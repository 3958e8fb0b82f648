"""The benchmark runner's forced-host-device children never need a chip:
they are pinned to the CPU backend, and a process that holds an
accelerator may not start one."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import common  # noqa: E402


def test_host_child_is_pinned_to_cpu():
    assert not common.holds_accelerator()
    env = common.host_device_env(8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"


def test_host_child_refused_on_accelerator(monkeypatch):
    monkeypatch.setattr(common.jax, "default_backend", lambda: "tpu")
    assert common.holds_accelerator()
    with pytest.raises(RuntimeError, match="chip_smoke.py --chips 4"):
        common.host_device_env(8)
