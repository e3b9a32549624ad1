"""Per-layer metric `decode_chunk_mean`: the mean chunk (window width) the
scheduler chose over the window's decode dispatches, from the recorded
windows (program counter).  Beside `decode_rows_mean` it shows whether
the elastic choice moves off the widest chunk as the batch fills."""


def read(run):
    chunks = [t.dec[1].shape[1] for t in run.ticks if t.dec is not None]
    return sum(chunks) / len(chunks) if chunks else None
