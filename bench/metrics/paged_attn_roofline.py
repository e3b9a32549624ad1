"""Per-layer metric `paged_attn_roofline`: see bench/metrics/_common.py."""
from bench.metrics._common import paged_attn_roofline as read  # noqa: F401
