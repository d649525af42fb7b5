"""Object-store backend abstraction.

Mirrors the reference's tempodb/backend split (backend.go:22-69,
raw.go:24-48): a raw byte-object layer (RawReader/RawWriter) under a
typed layer that knows about block metas, blooms, and the per-tenant
layout. Implementations: local filesystem (tempodb/backend/local),
in-memory mock (tempodb/backend/mocks.go) for tests; cloud backends
(GCS/S3/Azure) plug in behind the same Raw interface.
"""

from tempo_tpu.backend.base import (  # noqa: F401
    BlockMeta,
    CompactedBlockMeta,
    NotFound,
    RawBackend,
    TypedBackend,
)
from tempo_tpu.backend.faults import (  # noqa: F401
    FaultInjectingBackend,
    FaultPlan,
    retryable_error,
)
from tempo_tpu.backend.local import LocalBackend  # noqa: F401
from tempo_tpu.backend.mock import MockBackend  # noqa: F401


def make_raw_backend(kind: str, options: dict | None = None) -> RawBackend:
    """Backend factory (reference: tempodb.New backend selection,
    tempodb/tempodb.go:133-170). Cloud backends are imported lazily so
    the common local/mock path stays dependency-free.

    TEMPO_TPU_FAULTS (e.g. "read=0.01,corrupt=0.001,seed=7") wraps the
    result in a FaultInjectingBackend — the operator chaos knob; see
    backend/faults.py. A run that measures leaves it unset: injected
    errors and latency are not the path users pay for."""
    return _maybe_inject_faults(_make_raw_backend(kind, options))


def _maybe_inject_faults(raw: RawBackend) -> RawBackend:
    from tempo_tpu.backend import faults

    plan = faults.env_plan()
    if plan is not None:
        import logging

        logging.getLogger(__name__).warning(
            "TEMPO_TPU_FAULTS is armed — backend %s runs behind fault injection",
            type(raw).__name__,
        )
        return FaultInjectingBackend(raw, plan)
    return raw


def _make_raw_backend(kind: str, options: dict | None = None) -> RawBackend:
    options = options or {}
    if kind == "local":
        return LocalBackend(options.get("path", "blocks"))
    if kind == "mock":
        return MockBackend()
    if kind == "s3":
        from tempo_tpu.backend.s3 import S3Backend, S3Config

        return S3Backend(S3Config(**options))
    if kind == "gcs":
        from tempo_tpu.backend.gcs import GCSBackend, GCSConfig

        return GCSBackend(GCSConfig(**options))
    if kind == "azure":
        from tempo_tpu.backend.azure import AzureBackend, AzureConfig

        return AzureBackend(AzureConfig(**options))
    raise ValueError(f"unknown backend {kind!r} (have local|mock|s3|gcs|azure)")
