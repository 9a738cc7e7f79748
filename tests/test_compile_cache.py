"""The compile cache is placeable: `JAX_COMPILATION_CACHE_DIR` wins and the
helper sets nothing; unset, the cache sits at one fixed in-checkout path."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENTRY_POINTS = ["paddle_tpu/trainer_main.py", "tools/serve.py",
                "tools/train_dist.py", "chip_smoke.py"]

_PROBE = """
import jax
from paddle_tpu.utils import enable_compile_cache
before = jax.config.jax_compilation_cache_dir
a = enable_compile_cache()
b = enable_compile_cache()
import json
print(json.dumps([before, a, b, jax.config.jax_compilation_cache_dir]))
"""


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO, env=env,
                         capture_output=True, text=True, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_set_helper_sets_nothing(tmp_path):
    d = str(tmp_path / "elsewhere")
    before, a, b, after = _probe(d)
    assert before == d          # JAX read the variable itself
    assert a == b == after == d  # and the helper left it alone


def test_unset_uses_fixed_in_checkout_path():
    expected = os.path.join(REPO, ".jax_cache")
    runs = [_probe(None), _probe(None)]   # two processes, two calls each
    for before, a, b, after in runs:
        assert before is None
        assert a == b == after == expected


def test_default_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("path", ENTRY_POINTS)
def test_entry_point_uses_the_helper_and_names_no_other_dir(path):
    with open(os.path.join(REPO, path)) as f:
        src = f.read()
    assert "enable_compile_cache()" in src
    # the helper is the only place that may name a cache directory
    assert not re.search(r"jax_compilation_cache_dir|compilation_cache\."
                         r"set_cache_dir|JAX_COMPILATION_CACHE_DIR\"\]\s*=",
                         src)


def test_no_other_module_sets_a_cache_dir():
    hits = set()
    for top in ("paddle_tpu", "tools", "demo", "."):
        walk = (os.walk(os.path.join(REPO, top)) if top != "." else
                [(REPO, [], os.listdir(REPO))])
        for root, _, files in walk:
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(root, name)
                with open(path) as f:
                    if re.search(r"jax_compilation_cache_dir|set_cache_dir",
                                 f.read()):
                        hits.add(os.path.relpath(path, REPO))
    assert hits == {"paddle_tpu/utils/compile_cache.py"}
