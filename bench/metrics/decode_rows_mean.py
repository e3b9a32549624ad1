"""Per-layer metric `decode_rows_mean`: the mean number of live requests
(rows before padding) per decode dispatch over the window, from the
recorded windows (program counter)."""


def read(run):
    rows = [len(t.dec[0]) for t in run.ticks if t.dec is not None]
    return sum(rows) / len(rows) if rows else None
