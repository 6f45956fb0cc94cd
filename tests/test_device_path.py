"""The device path hides nothing: no silent fallback, no backend started at
import, no untracked file read by default, and a compile cache that can be
placed from outside."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _python(code_or_args, *, env_extra=None, env_drop=()):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu", **(env_extra or {}))
    args = (["-c", code_or_args] if isinstance(code_or_args, str)
            else code_or_args)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=300, env=env, cwd=REPO)


# ---------------------------------------------------------------------------
# Compile cache placement
# ---------------------------------------------------------------------------

_CACHE_DIR = """
import jax
from repro.launch.compile_cache import enable_compile_cache
returned = enable_compile_cache()
print(returned + "|" + str(jax.config.jax_compilation_cache_dir))
"""


def test_compile_cache_dir_comes_from_the_environment_when_set(tmp_path):
    p = _python(_CACHE_DIR, env_extra={
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode == 0, p.stderr
    returned, used = p.stdout.strip().splitlines()[-1].split("|")
    assert returned == used == str(tmp_path)


def test_compile_cache_dir_is_one_fixed_in_repo_path_when_unset():
    seen = set()
    for _ in range(2):            # two fresh processes
        p = _python(_CACHE_DIR, env_drop=("JAX_COMPILATION_CACHE_DIR",))
        assert p.returncode == 0, p.stderr
        seen.add(p.stdout.strip().splitlines()[-1])
    assert seen == {os.path.join(REPO, ".cache", "jax") + "|"
                    + os.path.join(REPO, ".cache", "jax")}


# ---------------------------------------------------------------------------
# No fallback that hides the device
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_to_run_without_a_tpu():
    p = _python([os.path.join(REPO, "chip_smoke.py")])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_dimension_semantics_raises_instead_of_dropping_params(monkeypatch):
    from jax.experimental.pallas import tpu as pltpu
    from repro.kernels import crossbar as xbk
    params = xbk._dimension_semantics(2, 1)
    assert params.dimension_semantics == ("parallel", "parallel",
                                          "arbitrary")

    def refuse(**_):
        raise TypeError("no such compiler parameter")

    monkeypatch.setattr(pltpu, "CompilerParams", refuse)
    with pytest.raises(TypeError):
        xbk._dimension_semantics(2, 1)


def test_importing_the_kernels_starts_no_backend():
    p = _python("""
import repro.kernels.ops, repro.sim
from jax._src import xla_bridge
print(xla_bridge.backends_are_initialized())
""")
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip().splitlines()[-1] == "False"


def test_default_blocks_ignore_a_persisted_table(tmp_path, monkeypatch):
    """Only opted-in autotuning reads the (git-ignored) block table; the
    default path takes its block sizes from code."""
    from repro.kernels import ops
    table = tmp_path / "autotune.json"
    table.write_text(json.dumps({"fwd_stacked|4|8|41|15": [8, 8, 8]}))
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(table))
    monkeypatch.delenv("REPRO_XBAR_AUTOTUNE", raising=False)
    saved, saved_tuned = dict(ops._BLOCK_CACHE), set(ops._TUNED_KEYS)
    ops._BLOCK_CACHE.clear()
    ops._TUNED_KEYS.clear()
    try:
        assert ops.block_config("fwd_stacked", 8, 41, 15, fold=4) \
            == ops._default_blocks(8, 41, 15)
        assert ("fwd_stacked", 4, 8, 41, 15) not in ops._TUNED_KEYS
        # an opted-in traced call (no timing possible) reads the table
        monkeypatch.setenv("REPRO_XBAR_AUTOTUNE", "1")
        assert ops.block_config("fwd_stacked", 8, 41, 15, fold=4) \
            == (8, 8, 8)
    finally:
        ops._BLOCK_CACHE.clear()
        ops._BLOCK_CACHE.update(saved)
        ops._TUNED_KEYS.clear()
        ops._TUNED_KEYS.update(saved_tuned)


@pytest.mark.parametrize("flag,expected", [("1", True), ("0", False)])
def test_force_kernels_flag_selects_either_scan_body(monkeypatch, flag,
                                                     expected):
    from repro.sim import compiled as csim
    monkeypatch.setenv("REPRO_SIM_FORCE_KERNELS", flag)
    assert csim.kernel_body_enabled() is expected
