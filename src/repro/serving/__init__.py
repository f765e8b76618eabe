from repro.serving.engine import Request, ServingEngine, TransformerExecutor
from repro.serving.galaxy import GalaxyHMPExecutor, GalaxyMLAExecutor
from repro.serving.kvcache import cache_bytes, make_cache
from repro.serving.kvpool import PagedKVPool, PoolExhausted
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampler import SamplerConfig, sample, sample_positions
from repro.serving.spec import (
    SpeculativeDecoder, longest_accepted_prefix, place_draft,
)

__all__ = [
    "Request", "ServingEngine", "TransformerExecutor", "GalaxyHMPExecutor",
    "GalaxyMLAExecutor",
    "PagedKVPool", "PoolExhausted", "PrefixCache",
    "make_cache", "cache_bytes", "SamplerConfig", "sample", "sample_positions",
    "SpeculativeDecoder", "longest_accepted_prefix", "place_draft",
]
