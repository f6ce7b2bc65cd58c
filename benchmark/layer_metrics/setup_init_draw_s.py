"""Of ``setup_init_s``, the seconds ``nn/weights.py`` spent making the
leaves on the host (seeding, numpy's sampling, filling): the ``draw_s`` that
it sums into the ``init/params`` span below each ``init`` of the run's
set-up. What drawing the weights on the device would take away."""
from benchmark import setup_trace


def read(run):
    found = setup_trace.records(run)
    if found is None:
        return None
    inits = {r["span_id"] for r in found if r["name"] == "init"}
    return sum(r["args"].get("draw_s", 0.0) for r in found
               if r["name"] == "init/params"
               and r["parent_span_id"] in inits)
