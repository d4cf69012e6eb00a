"""`import dvf_tpu` must never create a JAX backend client.

A chip belongs to the first process that initializes the backend, and
entry points choose the platform (``--platform``) and arm the compile
cache before first backend use: an import-time array creation would do
both too early — and make every jax-free parent (benchmarks/run_table.py,
`doctor`) hold the chip its children need.
"""

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
