"""`import madsim_tpu` must initialize no JAX backend.

On a chip host the first backend initialization claims the chip, and a
chip belongs to one process. A package whose import alone initialized a
backend would leave none for the worker processes a campaign starts.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_import_initializes_no_backend():
    code = ("import json, madsim_tpu, madsim_tpu.service.campaign\n"
            "from jax._src import xla_bridge\n"
            "print(json.dumps(sorted(xla_bridge._backends)))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []
