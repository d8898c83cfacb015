from repro.serving.api import (AdmissionQueueFull,  # noqa: F401
                               DeadlineExceeded, ResponseFuture,
                               ServeMetrics, ServeRequest, ServeResponse,
                               ServingEngine, available_engines,
                               create_engine, register_engine)
# importing engine registers "flame" / "text" in the registry
from repro.serving.engine import FlameEngine, TextServingEngine  # noqa: F401
from repro.serving.kv_cache import HistoryKVPool, KVCacheManager  # noqa: F401
