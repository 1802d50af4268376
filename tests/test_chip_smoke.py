"""``chip_smoke.py`` off the chip: its CPU rehearsal runs every phase in
this process, and no CPU run reports ``ok``."""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tiny_rehearsal_runs_every_phase_and_is_not_ok(smoke):
    backend = os.environ.get("REPRO_BACKEND")
    lines = []
    res = smoke.run(tiny=True, emit=lines.append)
    assert res["failed"] == []
    assert list(res["phases"]) == list(smoke.PHASES)
    assert [p["status"] for p in res["phases"].values()] == \
        ["ok"] * len(smoke.PHASES)
    assert len(lines) == len(smoke.PHASES)
    # the rehearsal drives the Pallas path, under the interpreter
    assert res["phases"]["device"]["backend"] == "pallas"
    assert res["phases"]["device"]["interpret"] is True
    assert res["phases"]["proxies"]["mega_stats"]["mega"] >= 1
    assert res["device"]["platform"] == "cpu"
    assert res["ok"] is False
    assert os.environ.get("REPRO_BACKEND") == backend   # env restored


def test_without_accelerator_stops_after_device_phase(smoke, monkeypatch,
                                                      capsys):
    res = smoke.run(emit=lambda s: None)
    assert list(res["phases"]) == ["device"]
    assert res["failed"] == ["device"] and res["ok"] is False
    # the script itself exits non-zero and prints no result line
    import repro.compile_cache
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "")
    assert smoke.main([]) != 0
    for line in capsys.readouterr().out.splitlines():
        if line.startswith("{"):
            assert "ok" not in json.loads(line)
