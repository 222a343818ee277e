"""Pluggable execution backends for the storage-backed runtime engine.

One :class:`ExecutionBackend` is one storage+invocation substrate a
:class:`~repro.api.DeploymentPlan` can execute on:

    emulated   virtual-clock object store + per-worker clocks — behavior-
               and cost-model-identical to the analytic stack (default)
    local      real wall-clock: S x d concurrent worker threads over a
               blocking in-memory (or filesystem) store — exercises the
               visibility/ordering races the virtual clock never hits,
               trains to bit-identical params
    aws / oss  real-platform stubs (boto3 / oss2 adapters not vendored)

Select by name anywhere a plan executes::

    plan.emulate(backend="local")
    session(...).emulate(backend="local")
    python -m repro emulate plan.json --backend local

Third-party backends register with :func:`register_backend`.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Union

from repro.serverless.backends.base import (  # noqa: F401
    ExecutionBackend,
    StepTiming,
    WorkerContext,
)
from repro.serverless.backends.cloud import (  # noqa: F401
    AliyunOssBackend,
    AwsS3Backend,
    BackendUnavailableError,
)
from repro.serverless.backends.emulated import (  # noqa: F401
    EmulatedBackend,
    EmulatedWorkerContext,
)
from repro.serverless.backends.local import (  # noqa: F401
    LocalBackend,
    LocalStore,
    LocalWorkerContext,
)
from repro.serverless.backends.process import (  # noqa: F401
    ProcessBackend,
    ProcessWorkerHandle,
    accelerator_conflict,
)

_REGISTRY: Dict[str, Callable[[], ExecutionBackend]] = {}


def register_backend(name: str,
                     factory: Callable[[], ExecutionBackend]) -> None:
    """Register a backend factory under ``name`` (overwrites allowed, so a
    real adapter can shadow a stub)."""
    _REGISTRY[name] = factory


def available_backends() -> tuple:
    """Registered backend names, stable order."""
    return tuple(sorted(_REGISTRY))


def _availability_of(name: str) -> Optional[str]:
    """None when backend ``name`` should work on this host; otherwise a short
    reason it will fail at open (missing client lib, no POSIX locks, ...)."""
    import importlib.util
    import os

    if name == "process":
        if os.name != "posix":
            return "needs POSIX file locks + signals"
        if importlib.util.find_spec("fcntl") is None:  # pragma: no cover
            return "fcntl module missing"
        return accelerator_conflict()
    client = {"aws": "boto3", "oss": "oss2"}.get(name)
    if client is not None and importlib.util.find_spec(client) is None:
        return f"{client} not installed"
    return None


def backend_availability() -> Dict[str, Optional[str]]:
    """Registered backend name -> None (available on this host) or a short
    reason it is not (used by backend-selection error messages and the CLI's
    ``--backend`` help)."""
    return {name: _availability_of(name) for name in available_backends()}


def _describe_backends() -> str:
    parts = []
    for name, why in backend_availability().items():
        parts.append(name if why is None else f"{name} (unavailable: {why})")
    return ", ".join(parts)


def get_backend(spec: Union[str, ExecutionBackend]) -> ExecutionBackend:
    """Resolve a backend: an instance passes through (pre-configured
    backends, e.g. ``LocalBackend(fs_root=...)``); a name constructs a fresh
    instance from the registry."""
    if isinstance(spec, ExecutionBackend):
        return spec
    try:
        factory = _REGISTRY[spec]
    except (KeyError, TypeError):
        raise KeyError(
            f"unknown execution backend {spec!r}; available: "
            f"{_describe_backends()}") from None
    return factory()


register_backend("emulated", EmulatedBackend)
register_backend("local", LocalBackend)
register_backend("process", ProcessBackend)
register_backend("aws", AwsS3Backend)
register_backend("oss", AliyunOssBackend)
