"""Two-tier transient-error classification.

A lone broad word ('internal', 'connection', 'socket', 'deadline') also
appears in deterministic failures — an XLA ``INTERNAL: ...`` compile bug
must not trigger the Evaluator's retry + recursive batch-split, which
recompiles at every new shape and burns chip time on an error that can
never succeed.  Specific transport-failure signatures stay one-hit transient.
"""

import pytest

from tpu_pipelines.utils.transient import is_transient_error


@pytest.mark.parametrize("msg", [
    # Transport failures, each by its own specific signature.
    "INTERNAL: stream closed: connection reset",
    "socket closed while sending the request",
    "socket hang up",
    "DEADLINE_EXCEEDED: deadline exceeded waiting for response",
    "UNAVAILABLE: service is temporarily unavailable",
    "ConnectionResetError: [Errno 104] connection reset by peer",
    "BrokenPipeError: [Errno 32] broken pipe",
    # gRPC status-code form and errno-timeout form (review finding: the
    # space-separated 'deadline exceeded' marker alone missed these).
    "DEADLINE_EXCEEDED",
    "ConnectionError: [Errno 110] Connection timed out",
    # Two broad words agreeing = network-shaped even without a signature.
    "INTERNAL: socket error during transfer",
])
def test_transient_signatures(msg):
    assert is_transient_error(msg)


@pytest.mark.parametrize("msg", [
    # Deterministic failures carrying ONE broad word must not be retried.
    "INTERNAL: during context [pre-optimization]: invalid HLO",
    "INTERNAL: Mosaic failed to compile TPU kernel",
    "ValueError: connection string is malformed",
    "deadline parameter must be positive",
    # Plainly deterministic errors.
    "ValueError: shapes do not match",
    "ImportError: no module named missing_dep",
    # OOM is explicitly never transient, even with a flake signature.
    "RESOURCE_EXHAUSTED: connection reset: out of memory",
])
def test_deterministic_not_transient(msg):
    assert not is_transient_error(msg)
