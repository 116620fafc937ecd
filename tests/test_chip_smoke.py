"""``chip_smoke.py`` kept alive on the CPU.

The script only means something on a TPU, but its control flow — the entry
points it calls, the telemetry it reads, the parity arithmetic — can rot in
any PR. Two guards: without a TPU it must refuse (non-zero exit, no ``ok``
line), and its one-chip phases must run end to end at toy size with the four
on-chip-only checks swapped out (the rehearsal the verify skill describes).
The four-chip phase is rehearsed by hand before a four-chip call, not here:
it is five more trainer runs.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY_MODEL = """\
vocab_size: 97
d_model: 64
n_layers: 4
n_heads: 4
d_ff: 128
max_seq_len: 128
dropout: 0.1
param_dtype: float32
compute_dtype: float32
attention: auto
"""


def test_refuses_without_a_tpu():
    """Held to the CPU, the script exits non-zero before any phase and
    prints no result line — it never calls a CPU run a chip run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.fixture
def toy_smoke(tmp_path, monkeypatch):
    """``chip_smoke`` pointed at toy copies of the shipped YAMLs."""
    sys.path.insert(0, REPO)
    import chip_smoke

    for name in ("train_config_dp.yaml", "optim_config.yaml", "serve_config.yaml"):
        shutil.copy(os.path.join(REPO, "configs", name), tmp_path / name)
    (tmp_path / "model_config.yaml").write_text(TOY_MODEL)
    monkeypatch.setattr(chip_smoke, "config_path", lambda name: str(tmp_path / name))
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path / "out"))
    monkeypatch.setattr(chip_smoke, "assert_flash_compiled", lambda cfg: "dense")
    monkeypatch.setattr(chip_smoke, "assert_kernel_in_program", lambda text, backend: None)
    monkeypatch.setattr(chip_smoke, "device_bytes", lambda device, key: 1)
    return chip_smoke


def test_one_chip_phases_rehearse_at_toy_size(toy_smoke, capsys):
    toy_smoke.phase_train(6)
    toy_smoke.phase_decode(0, new_tokens=16)
    toy_smoke.phase_serve(0, new_tokens=8)
    out = capsys.readouterr().out
    # On the CPU in fp32 the kernels ARE token-exact with the oracle.
    assert "parity=fused vs xla tokens_equal=128/128" in out
    assert "parity=fused_layers vs xla tokens_equal=128/128" in out
    assert "mode=plain backend=fused" in out and "equal_generate=4/4" in out
    assert "mode=speculative backend=fused_layers" in out
    assert not os.path.exists(os.path.join(REPO, "outputs", "chip_smoke"))
