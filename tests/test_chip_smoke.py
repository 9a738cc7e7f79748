"""chip_smoke.py's contract off the chip: the parent stays off JAX, and no
run without a TPU can exit 0 or print `"ok": true`."""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, cwd, **env):
    return subprocess.run([sys.executable] + argv, cwd=cwd,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
                          capture_output=True, text=True, timeout=300)


def test_parent_module_imports_without_jax():
    out = _run(["-c", "import sys, chip_smoke; "
                      "bad = [m for m in sys.modules if m == 'jax' or "
                      "m.startswith(('jax.', 'jaxlib', 'paddle_tpu'))]; "
                      "print('IMPORTED:', bad)"], REPO)
    assert out.returncode == 0, out.stderr
    assert "IMPORTED: []" in out.stdout


def test_cpu_run_exits_nonzero_and_never_says_ok():
    out = _run(["chip_smoke.py"], REPO)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout + out.stderr
    assert "no TPU" in out.stderr


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout + out.stderr
