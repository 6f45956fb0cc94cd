import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


# ---------------------------------------------------------------------------
# Optional-dependency shim: when hypothesis is not installed, property tests
# skip gracefully instead of erroring the whole module at import.
# ---------------------------------------------------------------------------

def _install_hypothesis_stub():
    try:
        import hypothesis  # noqa: F401
        return
    except ImportError:
        pass
    import types

    def given(*_a, **_kw):
        def deco(_f):
            def _skipped():
                pytest.skip("hypothesis not installed")
            _skipped.__name__ = _f.__name__
            _skipped.__doc__ = _f.__doc__
            return _skipped
        return deco

    class settings:  # noqa: N801 - mirrors the hypothesis API
        def __init__(self, *a, **kw):
            pass

        def __call__(self, f):
            return f

        @staticmethod
        def register_profile(*a, **kw):
            pass

        @staticmethod
        def load_profile(*a, **kw):
            pass

    def _strategy(*_a, **_kw):
        return None

    st = types.ModuleType("hypothesis.strategies")
    for name in ("lists", "floats", "integers", "booleans", "sampled_from",
                 "tuples", "one_of", "just", "text", "composite"):
        setattr(st, name, _strategy)

    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    mod.strategies = st
    mod.__stub__ = True
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st


_install_hypothesis_stub()


def run_subprocess(code: str, *, devices: int = 8, timeout: int = 600):
    """Run a python snippet in a fresh process with N fake devices.

    Multi-device tests must fork: jax locks the device count on first init.
    The child is pinned to the CPU: the parent has already touched JAX, and
    on a machine with an accelerator the parent holds it.
    """
    env = dict(os.environ,
               PYTHONPATH=SRC,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=REPO)
    if p.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={p.returncode}):\n{p.stdout}\n{p.stderr}")
    return p.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_subprocess
