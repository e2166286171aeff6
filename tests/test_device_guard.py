"""No path that finds no TPU carries on quietly: the kernel backend never
reads a device error as "no TPU", a TPU run refuses a non-Pallas backend
override, and ``chip_smoke.py`` fails without a chip."""
import importlib.util
import os
from types import SimpleNamespace

import jax
import pytest

from repro.kernels import dispatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _fake_devices(platform):
    return lambda: [SimpleNamespace(platform=platform, device_kind="fake")]


def test_default_backend_propagates_device_errors(monkeypatch):
    def broken():
        raise RuntimeError("backend failed to initialize")
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        dispatch.default_backend()


@pytest.mark.parametrize("env, want", [(None, "pallas"), ("pallas", "pallas"),
                                       ("interpret", None), ("ref", None)])
def test_tpu_serves_through_pallas_only(monkeypatch, env, want):
    if env is None:
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", env)
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu"))
    if want is None:
        with pytest.raises(RuntimeError, match="REPRO_KERNEL_BACKEND"):
            dispatch.get_backend()
    else:
        assert dispatch.get_backend() == want


@pytest.mark.parametrize("env, want", [(None, "ref"),
                                       ("interpret", "interpret")])
def test_cpu_backend_override(monkeypatch, env, want):
    if env is None:
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", env)
    monkeypatch.setattr(jax, "devices", _fake_devices("cpu"))
    assert dispatch.default_backend() == want


def test_chip_smoke_refuses_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out

