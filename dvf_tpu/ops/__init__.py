"""Filter op library + plugin registry.

Importing this package registers the builtin filters. The registry is the
framework's operator boundary — the counterpart of the reference's
``Worker`` subclassing mechanism (worker.py:78-80).
"""

from dvf_tpu.ops.registry import get_filter, list_filters, register_filter  # noqa: F401

# Builtin filter modules register themselves on import.
from dvf_tpu.ops import pointwise  # noqa: F401,E402
from dvf_tpu.ops import conv  # noqa: F401,E402
from dvf_tpu.ops import bilateral  # noqa: F401,E402
from dvf_tpu.ops import flow  # noqa: F401,E402
from dvf_tpu.ops import chains  # noqa: F401,E402
from dvf_tpu.ops import canny  # noqa: F401,E402
from dvf_tpu.ops import style  # noqa: F401,E402
from dvf_tpu.ops import sr  # noqa: F401,E402
from dvf_tpu.ops import histogram  # noqa: F401,E402
from dvf_tpu.ops import pallas_kernels  # noqa: F401,E402
from dvf_tpu.ops import denoise  # noqa: F401,E402
