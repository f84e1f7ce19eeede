"""Shared test configuration: the hermetic subprocess environment the
launcher/bench smokes share.  Nothing here touches a JAX backend: every
xdist worker imports this file, and only one process may hold a chip.
"""
import os


def subprocess_env(pythonpath="src", inherit=False):
    """The env dict for subprocess smokes (launchers, benches, -c
    scripts), built in ONE place instead of copy-pasted per test.

    Default is hermetic — a minimal PATH/HOME so the child can't pick up
    stray site configuration — with ``JAX_PLATFORMS`` propagated (CI
    pins cpu; a TPU runner's setting flows through).  ``pythonpath``
    is the child's import root relative to the repo cwd: ``"src"`` for
    library imports, ``"src:."`` when the child also imports the
    ``benchmarks`` package, ``None`` for tools that manage sys.path
    themselves.  ``inherit=True`` starts from the full parent environ
    instead (servers that bind sockets under sanitized CI env)."""
    env = dict(os.environ) if inherit else {"PATH": "/usr/bin:/bin",
                                            "HOME": "/root"}
    if pythonpath is not None:
        env["PYTHONPATH"] = pythonpath
    elif not inherit:
        env.pop("PYTHONPATH", None)
    env["JAX_PLATFORMS"] = os.environ.get("JAX_PLATFORMS", "cpu")
    return env
