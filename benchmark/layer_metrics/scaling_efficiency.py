"""The cell's rate per chip over the rate of the same step on device 0
alone, in per cent; the one-device rate is measured for a few seconds in the
same traced run."""


def read(run):
    alone = run.extras.get("one_device_rate")
    if not alone:
        return None
    return 100.0 * run.window.rate_per_chip / alone
