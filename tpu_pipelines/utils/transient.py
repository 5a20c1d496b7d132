"""Transient platform-error classification, shared by every retry site.

One list, one predicate: a run can flake with network-shaped failures
(a reset connection, a deadline, an unavailable service); retrying those
is worth chip time, retrying deterministic failures (ImportError, shape
errors, OOM, XLA compile bugs) is not.  The Evaluator's batch loop
classifies with this helper, so a newly observed flake signature is added
here.

Classification is two-tier (bare substrings like ``internal`` also match
deterministic ``INTERNAL: ...`` XLA compile bugs, so the Evaluator's retry
+ recursive batch-split burned chip time on failures that could never
succeed):

  - SPECIFIC signatures — phrases that only transport failures produce —
    classify as transient on a single hit;
  - BROAD words (``internal``, ``connection``, ``socket``, ``deadline``)
    individually appear in deterministic errors too; they classify as
    transient only when TWO of them agree, which deterministic messages
    essentially never produce.
"""

from __future__ import annotations

# One hit suffices: only a transport failure says these.
SPECIFIC_MARKERS = (
    "deadline exceeded",
    "deadline_exceeded",
    "timed out",
    "connection reset",
    "connection refused",
    "connection aborted",
    "broken pipe",
    "unavailable",
    "socket closed",
    "socket hang",
)

# Individually too broad (an XLA "INTERNAL: ..." compile bug is
# deterministic); transient only when two distinct words co-occur.
BROAD_MARKERS = ("internal", "connection", "socket", "deadline")


def is_transient_error(msg: str) -> bool:
    """Platform flakes worth retrying — never RESOURCE_EXHAUSTED (a retry
    at the same size would just burn chip time twice), and never a lone
    broad word like ``internal`` (deterministic XLA bugs match it too)."""
    low = msg.lower()
    if "resource_exhausted" in low:
        return False
    if any(m in low for m in SPECIFIC_MARKERS):
        return True
    return sum(1 for m in BROAD_MARKERS if m in low) >= 2
