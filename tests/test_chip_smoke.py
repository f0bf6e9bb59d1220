"""chip_smoke.py on the CPU: its refusal without a TPU, and its one-chip
phases end to end at a tiny size (the four-chip phase runs on virtual
devices in test_multidevice.py).  On the CPU the kernels dispatch to
the jnp references, so the TPU-only check that a compiled program holds
a Mosaic kernel is stubbed out here."""
import importlib.util
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
TINY = ["--nodes", "2000", "--hub-budget", "256", "--batch-size", "128",
        "--validate", "16", "--mix", "zipf", "--rate", "300",
        "--live-seconds", "1", "--json", ""]


@pytest.fixture
def chip_smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "_check_native_kernels",
                        lambda engine, batch: None)
    return mod


def test_main_refuses_without_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        chip_smoke.main([])
    assert "ok" not in capsys.readouterr().out


def test_one_chip_phases_on_cpu(chip_smoke, capsys):
    chip_smoke.one_chip(TINY)
    out = capsys.readouterr().out
    for phase in ("offline", "paths", "refresh", "live"):
        assert f"phase {phase}:" in out
    assert "offline: 0 mismatches of 16" in out
