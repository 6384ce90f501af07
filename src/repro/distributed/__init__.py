from .elastic import WorkerPool, FailureEvent

__all__ = ["WorkerPool", "FailureEvent"]
