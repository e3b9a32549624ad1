"""Per-layer metric `tick_host_ms`: the host's time per engine tick that
overlaps no wait on the device, from the program's own spans on the
profiler's clock: the mean over the traced window's ticks of the
`serving.engine.tick` span less its `*.wait` spans
(`bench.spans.SpanSummary.tick_host_ms`).  None on a trace without tick
spans."""


def read(run):
    return None if run.spans is None else run.spans.tick_host_ms()
