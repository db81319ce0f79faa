from .config import ServeConfig
from .engine import Request, ServeEngine
from .kv_cache import (PagePool, StateCache, cross_kv_bytes_per_seq,
                       kv_bytes_per_token, page_bytes, pool_bytes,
                       ssm_state_bytes_per_seq)
from .router import ReplicaRouter
from .spec import PromptLookupDrafter
from .stream import StreamCancelled, StreamError, TokenStream

__all__ = ["Request", "ServeConfig", "ServeEngine", "ReplicaRouter",
           "PagePool", "StateCache",
           "kv_bytes_per_token", "page_bytes", "pool_bytes",
           "ssm_state_bytes_per_seq",
           "cross_kv_bytes_per_seq", "PromptLookupDrafter",
           "TokenStream", "StreamCancelled", "StreamError"]
