"""``chip_smoke.py`` rehearsed on the CPU: its phases and checks at a tiny
size, and its refusal to report success without a TPU."""
import json
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    return chip_smoke


def test_smoke_refuses_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_phases_pass_at_cpu_size(chip_smoke, monkeypatch, tmp_path):
    """Every phase and agreement check passes; the one check that must fail
    off the chip is the compiled-kernel one (interpret mode lowers no TPU
    custom call)."""
    missed = []
    real_check = chip_smoke.check

    def check(ok, what):
        if not ok and "tpu_custom_call" in what:
            missed.append(what)
            return
        real_check(ok, what)

    monkeypatch.setattr(chip_smoke, "check", check)
    t = chip_smoke.run_smoke(n_refs=1500, n_queries=128, n_reference=16,
                             n_serve=24, dim=512, store_root=str(tmp_path))
    assert len(missed) == 1
    assert {"ingest", "cold_start", "steady_vpu", "steady_fused",
            "reference", "serve"} <= set(t)
    assert json.dumps(t)                  # plain floats, printable
