"""Serving: the paged engine, its scheduler, the asyncio frontend and
metrics.  Importing the package installs the process's program-build
listener (``repro.obs.install_build_listener``), so every build from
here on is logged and traced."""
from repro.obs.trace import install_build_listener
from repro.serve.engine import (PagedServeEngine, Request, ServeEngine,
                                request_key, supports_paging)
from repro.serve.frontend import (AsyncServeFrontend, FrontendClosedError,
                                  QueueFullError, StreamHandle)
from repro.serve.metrics import Histogram, ServeMetrics
from repro.serve.paging import (BlockPool, PrefixCache, blocks_for,
                                set_block_tables)
from repro.serve.scheduler import Scheduler, SeqState, TickPlan

install_build_listener()
