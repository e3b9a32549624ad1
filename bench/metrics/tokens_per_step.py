"""Per-layer metric `tokens_per_step`: see bench/metrics/_common.py."""
from bench.metrics._common import tokens_per_step as read  # noqa: F401
