"""Test-support harnesses (fault injection, failing sinks).

Importable from examples and simulated hosts too — everything here is
deterministic and dependency-free; nothing imports pytest.
"""
from .faults import (  # noqa: F401
    FailingSink,
    FaultInjector,
    SlowSink,
    StragglerDelay,
    TensorFault,
)
