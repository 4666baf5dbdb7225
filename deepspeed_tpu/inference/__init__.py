"""Inference engine + serving layer. Parity: reference
``deepspeed/inference/`` (engine); the continuous-batching serving layer
(``serving.py``) with its resilience machinery (deadlines, load
shedding, quarantine, crash-recoverable journal — ``journal.py``) is
this repo's production-traffic addition (docs/serving.md)."""

from .engine import InferenceEngine
from .serving import (ServingConfig, ServingEngine, PrefixCacheConfig,
                      describe_prefix_cache,
                      Request, ServingError, QueueFullError,
                      ServingStalledError, CircuitOpenError,
                      OK, SHED, DEADLINE, POISONED, OUTCOMES)
from .router import (ReplicaRouter, RouterConfig, ReplicaHandle,
                     LocalReplica, ProcessReplica,
                     HEALTHY, SUSPECT, DRAINING, DEAD)

__all__ = ["InferenceEngine", "ServingEngine", "ServingConfig",
           "PrefixCacheConfig",
           "describe_prefix_cache", "Request",
           "ServingError", "QueueFullError", "ServingStalledError",
           "CircuitOpenError", "OK", "SHED", "DEADLINE", "POISONED",
           "OUTCOMES",
           "ReplicaRouter", "RouterConfig", "ReplicaHandle",
           "LocalReplica", "ProcessReplica",
           "HEALTHY", "SUSPECT", "DRAINING", "DEAD"]
