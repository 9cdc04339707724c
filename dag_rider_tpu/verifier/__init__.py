from dag_rider_tpu.verifier.base import (
    KeyRegistry,
    Verifier,
    VerifierCompileError,
    VerifierUnavailableError,
    VertexSigner,
)
from dag_rider_tpu.verifier.cpu import CPUVerifier, NullVerifier
from dag_rider_tpu.verifier.faults import (
    VerifierFaultInjector,
    VerifierFaultPlan,
)
from dag_rider_tpu.verifier.pipeline import VerifierPipeline
from dag_rider_tpu.verifier.resilient import ResilientVerifier

__all__ = [
    "KeyRegistry",
    "Verifier",
    "VertexSigner",
    "CPUVerifier",
    "NullVerifier",
    "VerifierPipeline",
    "ResilientVerifier",
    "VerifierCompileError",
    "VerifierUnavailableError",
    "VerifierFaultInjector",
    "VerifierFaultPlan",
]
