"""Per-layer metric `device_idle_pct`: see bench/metrics/_common.py."""
from bench.metrics._common import device_idle_pct as read  # noqa: F401
