"""Per-layer metric `step_mfu`: see bench/metrics/_common.py."""
from bench.metrics._common import step_mfu as read  # noqa: F401
