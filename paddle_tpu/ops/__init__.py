"""Op library: importing this package registers every lowering rule."""

from . import math  # noqa: F401
from . import tensor  # noqa: F401
from . import nn  # noqa: F401
from . import loss  # noqa: F401
from . import rnn  # noqa: F401
from . import sequence  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import control  # noqa: F401
from . import tensor_array  # noqa: F401
from . import detection  # noqa: F401
from . import quantize  # noqa: F401
from . import beam  # noqa: F401
from . import loss_extra  # noqa: F401
from . import pallas_attention  # noqa: F401
from . import paged_attention  # noqa: F401
from . import extra_nn  # noqa: F401
from . import decoder_block  # noqa: F401
from . import moe  # noqa: F401
from . import linear_attention  # noqa: F401
from . import state_space  # noqa: F401
from . import selective_scan  # noqa: F401
from . import sparse_attention  # noqa: F401
