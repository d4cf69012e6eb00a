"""Neural filter models.

The reference has no neural models — its one op is ``cv2.bitwise_not``
(inverter.py:41). Two families ship here: a Johnson-style feed-forward
transformer net (the flagship filter, BASELINE.json configs[4], with a
small VGG encoder providing perceptual features for training) and an
ESPCN sub-pixel super-resolution net (enhancement family; all FLOPs at
low resolution — built for the MXU). A third, FastDVDnet video denoising
(:mod:`dvf_tpu.models.fastdvdnet`: two stages of a three-scale U-Net over a
five-frame window), is served streamed by ``ops/denoise.py``.

Models are plain functional JAX: ``init(rng, ...) -> params`` pytrees and
``apply(params, batch) -> batch`` functions, with explicit
``PartitionSpec`` trees for tensor parallelism over the mesh ``model`` axis
(:func:`dvf_tpu.models.style_transfer.param_pspecs`).
"""

from dvf_tpu.models.style_transfer import (  # noqa: F401
    StyleNetConfig,
    init_style_net,
    apply_style_net,
    param_pspecs,
)
from dvf_tpu.models.espcn import (  # noqa: F401
    EspcnConfig,
    apply_espcn,
    init_espcn,
)
from dvf_tpu.models.fastdvdnet import (  # noqa: F401
    FastDvdConfig,
    apply_fastdvdnet,
    init_fastdvdnet,
)
from dvf_tpu.models.vgg import VGGConfig, init_vgg, vgg_features  # noqa: F401
