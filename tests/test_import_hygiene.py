"""`import dvf_tpu` must never create a JAX backend client.

A chip belongs to the first process that initializes the backend, and
entry points choose the platform (``--platform``) and arm the compile
cache before first backend use: an import-time array creation would do
both too early — and make every jax-free parent (`doctor`) hold the
chip its children need.

And the package stands alone: no module under ``dvf_tpu/`` opens or
imports a file of the repository around it.
"""

import os
import shutil
import subprocess
import sys


def test_import_does_not_initialize_backend():
    code = (
        "import os; os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import dvf_tpu\n"
        "import dvf_tpu.benchmarks, dvf_tpu.cli\n"
        "import dvf_tpu.runtime.pipeline, dvf_tpu.transport.zmq_ingress\n"
        "from jax._src import xla_bridge\n"
        "raise SystemExit(0 if not xla_bridge.backends_are_initialized() else 3)\n"
    )
    p = subprocess.run([sys.executable, "-c", code], timeout=180)
    assert p.returncode == 0, "importing dvf_tpu initialized a JAX backend"


_STANDS_ALONE = """
import json, os, sys
import dvf_tpu
from dvf_tpu.control import planner as pl
from dvf_tpu.models import analysis
from dvf_tpu.transport.codec import entropy_pool_size

assert os.path.dirname(os.path.abspath(dvf_tpu.__file__)) == os.path.join(
    sys.argv[1], "dvf_tpu"), dvf_tpu.__file__
best, comp = pl.plan_search(
    [pl.Plan(batch_size=2), pl.Plan(batch_size=4)],
    lambda plan: {"fps": 100.0 * plan.batch_size})
print(json.dumps({"plan": best.to_doc(), "comparison": comp,
                  "entropy_pool_size": entropy_pool_size(4)}))
analysis.main(["--json"])
"""


def test_the_package_stands_without_the_repository_around_it(tmp_path):
    """``dvf_tpu/`` alone, copied somewhere else, plans, sizes the entropy
    pool and writes the roofline report exactly as it does in the
    checkout: nothing in it reads a file beside the package."""
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copytree(
        os.path.join(checkout, "dvf_tpu"), tmp_path / "dvf_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "*.so", "*.srchash"))

    def run(root):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root))
        p = subprocess.run(
            [sys.executable, "-c", _STANDS_ALONE, str(root)], cwd=str(root),
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, timeout=180)
        assert p.returncode == 0, p.stderr[-2000:]
        return p.stdout

    alone = run(tmp_path)
    assert '"entropy_pool_size": 3' in alone
    assert alone == run(checkout)
