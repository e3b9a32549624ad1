"""Per-layer metric `prefill_pad_pct`: the share of the prefill program's
token slots that carry no prompt token, over the window's prefill
dispatches: 100 × (1 − prompt tokens ÷ (padded rows × padded length)).

The program records the padded shape of each dispatch on its plan
(``ModelBackend.last_prefill_plan``, a ``PrefillPlan``); a program that
records none reads None."""


def read(run):
    slots = tokens = 0
    for t in run.ticks:
        rows = getattr(t.plan, "padded_rows", 0)
        if rows:
            slots += rows * t.plan.padded_len
            tokens += sum(n for *_, n in t.plan)
    return 100.0 * (1.0 - tokens / slots) if slots else None
