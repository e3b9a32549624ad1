"""Per-layer metric `queue_wait_p90_ms`: see bench/metrics/_common.py."""
from bench.metrics._common import queue_wait_p90_ms as read  # noqa: F401
