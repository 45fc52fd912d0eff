"""The clustering path stands apart from the forward-pass model zoo.

Importing every clustering module must load no module of ``repro.models``,
``repro.configs`` or ``repro.serve.engine``: the zoo sits beside the
clustering system, and nothing on the path ``text/ -> kernels/ops -> core/
-> distrib/ -> serve/cluster_service`` may come to depend on it.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Runs in a fresh interpreter, so only what these imports pull in is loaded.
_PROBE = textwrap.dedent("""
    import importlib, json, pkgutil, sys

    import repro.core, repro.distrib, repro.kernels, repro.resilience, repro.text
    import repro.common, repro.serve.cluster_service

    for pkg in (repro.core, repro.text, repro.kernels, repro.distrib,
                repro.resilience):
        for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + "."):
            importlib.import_module(m.name)
    print(json.dumps(sorted(m for m in sys.modules if m.startswith("repro"))))
""")

ZOO = ("repro.models", "repro.configs", "repro.serve.engine")


def test_clustering_path_imports_no_model_zoo():
    out = subprocess.run(
        [sys.executable, "-c", _PROBE],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    loaded = json.loads(out.stdout.strip().splitlines()[-1])
    assert "repro.distrib.cluster" in loaded and "repro.core.kmeans" in loaded
    zoo = [m for m in loaded
           if m in ZOO or m.startswith(tuple(z + "." for z in ZOO))]
    assert not zoo, f"clustering modules import the model zoo: {zoo}"
