"""Arithmetic shared by the per-layer readers.  Each reader returns None
when its run has nothing to read (no trace, no dispatch in the window)."""

from __future__ import annotations

from bench import stats

# the decode and prefill programs, by the host span waiting for them
DECODE_SPAN = "bench.backend.decode_dispatch"
PREFILL_SPAN = "bench.backend.prefill_tick"
# the paged-attention kernel, by the name of its Pallas call's op
# (``%chunked_paged_attn.N``), so that another Pallas kernel of the same
# program is not counted; a program that leaves the call unnamed shows it
# as ``%closed_call.N``
PAGED_KERNEL = (r'^%(chunked_paged_attn|closed_call)(\.\d+)? = .*'
                r'custom_call_target="tpu_custom_call"')
TICK_MARK = "bench.mark.tick"


def queue_wait_p90_ms(run):
    waits = [m.admit_time - m.arrival_time for m in run.sampled
             if m is not None and m.admit_time >= 0]
    return 1e3 * stats.percentile(waits, 90) if waits else None


def tokens_per_step(run):
    steps = sum(1 for t in run.ticks if t.dec is not None)
    if not steps:
        return None
    return sum(t.n_committed for t in run.ticks) / steps


def decode_step_ms(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module_stats(DECODE_SPAN)
    return 1e3 * secs / n if n else None


def prefill_ms_per_ktok(run):
    if run.trace is None:
        return None
    secs, n = run.trace.module_stats(PREFILL_SPAN)
    tokens = run.trace.marks.get(TICK_MARK, {}).get("prefill_tokens", 0.0)
    return 1e3 * secs / (tokens / 1e3) if n and tokens else None


def paged_attn_roofline(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds(PAGED_KERNEL)
    bound_s = run.trace.marks.get(TICK_MARK, {}).get("attn_bound_s", 0.0)
    return 100.0 * bound_s / kernel_s if kernel_s and bound_s else None


def step_mfu(run):
    from bench.harness import tick_flops
    if run.peak is None or not run.ticks:
        return None
    wall = sum(t.t1 - t.t0 for t in run.ticks)
    work = sum(tick_flops(run.model, run.spec, t) for t in run.ticks)
    return 100.0 * work / (wall * run.peak["flops"]) if wall and work \
        else None


def device_idle_pct(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
