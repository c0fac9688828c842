"""Import hygiene: importing shadow1_tpu must never touch a JAX backend.

The driver's dryrun_multichip spawns a CPU-sandboxed child *after* importing
the package in the parent; any module-level eager JAX op (e.g. a jnp
constant) initializes the ambient backend at import time, and a parent that
holds the TPU keeps every child off the chip.  This cost three consecutive rounds of red MULTICHIP artifacts
(rng.py in r2, engine.py:80 in r3).  This test locks the rule in: a fresh
subprocess imports the package plus every submodule and asserts that
``jax._src.xla_bridge._backends`` stays empty.

Reference analogue: the reference has no equivalent hazard (C has no import
side effects); this is a JAX-specific invariant.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _all_submodules():
    """Enumerate every module from the filesystem, not pkgutil: import-based
    walkers silently skip subpackages that fail to import, which is exactly
    the failure class this test exists to catch."""
    pkg_dir = os.path.join(REPO, "shadow1_tpu")
    names = []
    for dirpath, _dirnames, filenames in os.walk(pkg_dir):
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fn), REPO)
            mod = rel[: -len(".py")].replace(os.sep, ".")
            if mod.endswith(".__init__"):
                mod = mod[: -len(".__init__")]
            names.append(mod)
    assert "shadow1_tpu" in names and "shadow1_tpu.core.engine" in names
    return sorted(names)


def test_import_initializes_no_backend():
    mods = _all_submodules()
    # __main__ runs the CLI; skip it (importing it is harmless but it is not
    # part of the library surface).
    mods = [m for m in mods if not m.endswith("__main__")]
    prog = (
        "import sys\n"
        "mods = sys.argv[1:]\n"
        "for m in mods:\n"
        "    __import__(m)\n"
        "import jax._src.xla_bridge as xb\n"
        "assert xb._backends == {}, (\n"
        "    'importing %r initialized JAX backend(s): %r'\n"
        "    % (mods, list(xb._backends)))\n"
        "print('IMPORT_HYGIENE_OK')\n"
    )
    env = dict(os.environ)
    # Deliberately do NOT force JAX_PLATFORMS=cpu here: the point is that the
    # import alone must not initialize *any* backend, ambient or otherwise.
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", prog, *mods],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert out.returncode == 0, (
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    )
    assert "IMPORT_HYGIENE_OK" in out.stdout
