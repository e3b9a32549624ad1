"""Per-layer metric `prefill_ms_per_ktok`: see bench/metrics/_common.py."""
from bench.metrics._common import prefill_ms_per_ktok as read  # noqa: F401
