"""Host→HBM staging pipeline (TPU-native consumer side of the ingest ladder)."""

from .packing import pack_flat, batch_slices, PackStats  # noqa: F401
from .device_loader import DeviceLoader  # noqa: F401
from .ingest_service import (serve_ingest, RemoteIngestLoader,  # noqa: F401
                             ingest_worker_main)
from .page_cache import (PageCacheReader, PageCacheWriter,  # noqa: F401
                         open_reader as open_page_reader, page_path)
from .autotune import (Autotuner, Knob, ingest_knob_space,  # noqa: F401
                       maybe_autotuner, serving_knob_space)
from .fingerprint import autotune_key, host_shape  # noqa: F401
from .data_service import (Dispatcher, DataServiceWorker,  # noqa: F401
                           DataServiceLoader)

__all__ = ["pack_flat", "batch_slices", "PackStats",
           "serve_ingest", "RemoteIngestLoader", "ingest_worker_main",
           "DeviceLoader", "PageCacheReader", "PageCacheWriter",
           "open_page_reader", "page_path",
           "Autotuner", "Knob", "ingest_knob_space", "serving_knob_space",
           "maybe_autotuner", "autotune_key", "host_shape",
           "Dispatcher", "DataServiceWorker", "DataServiceLoader"]
