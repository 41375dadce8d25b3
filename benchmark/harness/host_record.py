"""The program's own host record (``eventstreamgpt_tpu/utils/scopes.py``,
`recorded`) as the per-layer readers take it: the ``es.host/plan`` spans that
lie inside the run's window, with the counts the program wrote on them where
the plans were made. A program that keeps no record, a run without a window
and a window without such a span all read nothing, never 0."""

from __future__ import annotations


def plan_spans(record: dict):
    """``(the window's plan spans, every span's self seconds by seq)``."""
    from eventstreamgpt_tpu.utils import scopes

    window = record.get("window")
    if window is None or not hasattr(scopes, "recorded"):
        return None
    spans = scopes.recorded()
    plans = [s for s in spans if s.name == "plan" and s.start >= window[0] and s.end <= window[1]]
    return (plans, scopes.self_seconds(spans)) if plans else None


def count_ratio(record: dict, over: str, under: str):
    """100 x the window's sum of one count of the plan spans over another's."""
    found = plan_spans(record)
    if found is None:
        return None
    below = sum(s.counts.get(under, 0) for s in found[0])
    return 100.0 * sum(s.counts.get(over, 0) for s in found[0]) / below if below else None
