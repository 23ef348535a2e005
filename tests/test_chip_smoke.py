"""chip_smoke.py off the chip: it must fail, quickly, and print no result.

What the script does ON the chip is checked by running it there (the
driver does; ``chiprun -- python chip_smoke.py``).  Here: the launcher
never imports jax, a CPU platform is refused within seconds, and the
script alone in a directory is refused too.
"""

import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and rec.get("ok"):
            return False
    return True


def test_chip_smoke_fails_fast_on_the_cpu():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, SMOKE], cwd=REPO, capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "needs platform 'tpu'" in proc.stderr
    assert _no_result(proc.stdout)


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def test_launcher_does_not_import_jax():
    """A parent that has touched jax holds the chip; the launcher's module
    level and its launcher-side functions must stay off it."""
    code = ("import sys; sys.argv=['chip_smoke.py']; "
            "import importlib.util as u; "
            f"s=u.spec_from_file_location('cs', {SMOKE!r}); "
            "m=u.module_from_spec(s); s.loader.exec_module(m); "
            "m.child_env(m.argparse.Namespace(tiny_cpu=False), None); "
            "m.chip_env(2); "
            "assert 'jax' not in sys.modules, 'launcher imported jax'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
