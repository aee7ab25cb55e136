"""fluid-serve: TPU-native inference serving (see docs/SERVING.md).

The north star says this framework must serve heavy traffic; TPU serving
lives or dies on (a) never recompiling on the request path and (b)
keeping the chip fed with full batches. The subsystem is three layers,
each independently testable:

- `serve.registry` — ModelRegistry: loads `save_inference_model` dirs
  (sha256-verified against their MANIFEST.json) into warmed
  PreparedProgram handles, hot-swaps new versions behind an atomic
  pointer, retires old ones after in-flight requests drain;
- `serve.bucketing` — BucketLadder + planner: pads every request onto an
  ahead-of-time-compiled ladder of shapes, so steady-state traffic
  causes ZERO recompiles (the observatory attributes any miss on a
  serving handle as `padding_bucket` — a ladder bug, not a cache bug);
- `serve.batcher` — MicroBatcher: per-bucket queues coalescing
  concurrent requests up to the top rung or `batch_timeout_ms`, bounded
  admission (QueueFullError fast-reject) and per-request deadlines.

`serve.InferenceServer` fronts all three. Load-test with
`tools/serve_loadgen.py` (`serve_p50_us`/`serve_p99_us`/`serve_qps`/
`serve_recompiles`).
"""

from __future__ import annotations

from .batcher import MicroBatcher, SlotScheduler  # noqa: F401
from .bucketing import (DEFAULT_ROWS_LADDER, BucketLadder,  # noqa: F401
                        load_trace, plan_request, predicted_padding_waste,
                        save_trace, trace_request, warm_feed_shapes)
from .decode import (DecodeEngine, GenerationResult,  # noqa: F401
                     GenerationStream)
from .errors import (BadRequestError, CacheExhaustedError,  # noqa: F401
                     DeadlineExceededError, KVTransferError,
                     ModelNotFoundError, ModelUnavailableError,
                     QueueFullError, ServeError)
from .kvcache import (PagedKVCache, block_residency_nbytes,  # noqa: F401
                      blocks_for_budget)
from .registry import (DecodeModel, ModelRegistry,  # noqa: F401
                       ModelVersion, read_decode_signature,
                       read_model_manifest)
from .server import InferenceServer, ServeConfig  # noqa: F401
