"""Host milliseconds the program itself spends making one dispatch's plans:
self time of its ``es.host/plan`` spans inside the window over the window's
dispatches (`harness/host_record.py`). A chunk the program planned and the job
did not dispatch (an epoch's short last one) is in the time. The harness's
``feed_plan_ms`` times the same layer from outside, around ``next_plans()``."""

from benchmark.harness import host_record

LAYER = "feed"
UNIT = "ms"
MOVES = "train_events_per_s"
SOURCE = "program_span"


def read(record: dict):
    found = host_record.plan_spans(record)
    if found is None:
        return None
    plans, own = found
    dispatches = record.get("counters", {}).get("dispatches") or len(plans)
    return 1000.0 * sum(own[s.seq] for s in plans) / dispatches
