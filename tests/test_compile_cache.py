"""The compile-cache rule (utils/compile_cache.py), in real processes.

``JAX_COMPILATION_CACHE_DIR`` set -> JAX holds the variable's value after
each entry point that compiles and nothing else is set; unset -> the same
``<checkout>/.jax_cache`` whatever the working directory. Both entry
points: a training worker's ``bootstrap.initialize()`` and a predictor's
``LLMModel.load()``.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENTRY = {
    "initialize": """
from kubeflow_tpu.rendezvous import bootstrap
bootstrap.initialize()
""",
    "load": """
from kubeflow_tpu.models import llama
from kubeflow_tpu.serving import LLMModel
cfg = llama.llama_tiny()
model = LLMModel("m", llama.init_params(jax.random.key(0), cfg), cfg,
                 max_batch=2, max_seq=32)
model.load()
model.unload()
""",
}
SCRIPT = """
import json
import jax
at_import = jax.config.jax_compilation_cache_dir
{entry}
print(json.dumps({{"at_import": at_import,
                  "after": jax.config.jax_compilation_cache_dir}}))
"""


def _spawn(entry, cwd, cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if cache_env:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    return subprocess.Popen(
        [sys.executable, "-c", SCRIPT.format(entry=ENTRY[entry])],
        cwd=cwd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


@pytest.mark.parametrize("entry", sorted(ENTRY))
def test_cache_dir_is_the_environments_or_the_checkouts(entry, tmp_path):
    placed = str(tmp_path / "placed")
    cwd_a, cwd_b = tmp_path / "a", tmp_path / "b"
    cwd_a.mkdir()
    cwd_b.mkdir()
    procs = {"env": _spawn(entry, cwd_a, placed),
             "a": _spawn(entry, cwd_a, None),
             "b": _spawn(entry, cwd_b, None)}
    got = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=300)
        assert proc.returncode == 0, err[-2000:]
        got[name] = json.loads(out.strip().splitlines()[-1])
    # the variable was read by JAX itself, before any of our code ran
    assert got["env"] == {"at_import": placed, "after": placed}
    fixed = os.path.join(REPO, ".jax_cache")
    for name in ("a", "b"):
        assert got[name] == {"at_import": None, "after": fixed}
