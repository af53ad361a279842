"""chip_smoke.py off the chip: it must refuse to pass, keep its last-line
contract, and its leg A/B control flow (FT steps vs the plain loop, kill +
live heal back onto the device) must hold at a toy size.

The toy size and the CPU platform are steered from here — chip_smoke.py has
no switch a chip run could take by accident."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu: non-zero exit, last
    stdout line says ok false — it never trains on the CPU and passes."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120, cwd=REPO,
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "tpu" in last["reason"]


def test_last_line_shape():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert chip_smoke.result_line(True, dev) == (
        '{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite", '
        '"count": 1}}'
    )
    assert chip_smoke.refusal(dev, chips=1) is None
    assert "--chips 4" in chip_smoke.refusal(dev, chips=4)
    assert json.loads(chip_smoke.result_line(False, dev, "why"))["reason"] == "why"


def test_interpreted_kernels_cannot_pass():
    """On this CPU backend both Pallas modules resolve to interpret mode,
    which the chip path treats as a hard error; and an HLO without the
    Mosaic custom call is rejected."""
    with pytest.raises(RuntimeError, match="interpret mode"):
        chip_smoke.require_compiled_kernels()
    with pytest.raises(AssertionError, match="Mosaic"):
        chip_smoke.require_mosaic("func.func @main() { stablehlo.add }", "x")
    chip_smoke.require_mosaic("stablehlo.custom_call @tpu_custom_call", "x")


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setattr(chip_smoke, "WIDTHS", dict(
        vocab_size=256, d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
        max_seq_len=128,
    ))
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(chip_smoke, "require_mosaic", lambda text, what: None)
    monkeypatch.setattr(chip_smoke, "LEG_A", dict(layers=2, batch=2, steps=3))
    monkeypatch.setattr(
        chip_smoke, "LEG_B", dict(layers=2, batch=2, steps=6, kill_at=2)
    )
    monkeypatch.setattr(chip_smoke, "OP_TIMEOUT_S", 30.0)
    monkeypatch.setattr(chip_smoke, "LEG_DEADLINE_S", 120.0)


def _events(capsys, leg):
    out = capsys.readouterr().out
    rows = [json.loads(line) for line in out.splitlines() if line.startswith("{")]
    return [r for r in rows if r.get("leg") == leg]


def test_leg_a_control_flow(toy, capsys):
    """FT steps through Manager/DDP/Optimizer equal the plain loop."""
    chip_smoke.leg_a(seed=0)
    rows = _events(capsys, "A")
    steps = [r for r in rows if r.get("event") == "step"]
    assert len(steps) == 3 and all(r["committed"] for r in steps)
    done = next(r for r in rows if r.get("event") == "done")
    assert done["ft_losses"] == done["plain_losses"]


def test_leg_b_kill_and_heal(toy, capsys):
    """Replica 1 dies, restarts from fresh state, heals live from replica
    0 onto its device and both end bitwise equal (leg_b raises if not)."""
    chip_smoke.leg_b(seed=0)
    rows = _events(capsys, "B")
    assert any(r.get("event") == "killed" for r in rows)
    ok = next(r for r in rows if r.get("event") == "kill_heal_ok")
    assert ok["bitwise_equal_after_heal"] is True
    assert ok["heals"][0]["incarnation"] == 1 and ok["heals"][0]["bytes"] > 0
