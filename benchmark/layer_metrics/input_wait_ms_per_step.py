"""Milliseconds per step that ``fit`` blocked on the input pipeline: the
registry's ``input_wait_seconds`` over the window, less what the iterator's
own run-ahead brake held the pipeline for (that wait is for the device, not
for input), over the steps."""


def read(run):
    win = run.window
    if not win.steps or not win.counters["input_waits"]:
        return None
    waited = win.counters["input_wait_seconds"] - win.brake_seconds
    return max(0.0, waited) / win.steps * 1e3
