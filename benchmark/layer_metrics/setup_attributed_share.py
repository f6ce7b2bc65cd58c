"""Of ``setup_s``, the share in per cent during which the thread that ran
``init`` was inside a kept span of the program (``init``, ``compile/<fn>``,
a compile's phases ``jax/*``, ``pw/place_model``; their union): what of
set-up the program can name. The rest is the benchmark's own (the pool, its
fetch of the loss after each warm-up ``fit``, its wait for the cost
capture), the program's imports, and the device running the warm-up
steps."""
from benchmark import setup_trace

NAMED = ("init", "pw/place_model")
PREFIXES = ("compile/", "jax/")


def read(run):
    found = setup_trace.records(run)
    if found is None or not run.setup_s:
        return None
    threads = {r["tid"] for r in found if r["name"] == "init"}
    inside = setup_trace.covered(
        found, lambda name: name in NAMED or name.startswith(PREFIXES),
        threads)
    return 100.0 * inside / run.setup_s
