"""The persistent compilation cache helper: the environment's directory
when it names one, else one fixed directory inside the checkout."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
_PROBE = (
    "import json, jax\n"
    "from repro.launch.compile_cache import use_compile_cache\n"
    "a = use_compile_cache(); b = use_compile_cache()\n"
    "print(json.dumps([a, b, jax.config.jax_compilation_cache_dir]))\n"
)


def _probe(cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_no_other_set(tmp_path):
    d = str(tmp_path / "cache")
    assert _probe(d) == [d, d, d]


def test_default_dir_is_fixed_inside_checkout():
    first, second = _probe(), _probe()
    want = str(ROOT / ".jax_cache")
    assert first == second == [want, want, want]
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
