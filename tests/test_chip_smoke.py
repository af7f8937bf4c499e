"""`chip_smoke.py` guarded on the CPU: its phases pass at the reduced
generator width, and the script itself refuses to report success
anywhere but on a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_pass_at_reduced_width():
    """Every phase but the platform check, on a small corpus: all
    requests done, every counter zero, retrieval and SCR equal to the
    references, logits finite."""
    from repro.configs import get_reduced
    smoke = _load_smoke()
    lines = []
    bad = smoke.run_smoke(0, 200, get_reduced("qwen25_0_5b"),
                          log=lines.append)
    assert bad == [], bad
    assert any("done=16" in ln for ln in lines), lines


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """Under JAX_PLATFORMS=cpu, from the repo or as a lone copy with none
    of the repo beside it, the script exits nonzero and prints no ok
    line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cwd = ROOT
    if where == "alone":
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        env.pop("PYTHONPATH", None)
        cwd = tmp_path
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
