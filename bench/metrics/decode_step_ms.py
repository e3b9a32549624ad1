"""Per-layer metric `decode_step_ms`: see bench/metrics/_common.py."""
from bench.metrics._common import decode_step_ms as read  # noqa: F401
