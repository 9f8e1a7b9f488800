"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device-busy intervals over the window.  Layer:
the host loop of the scan driver (``FedSAEServer._run_scan``), which
syncs once a block for the stats, the eval and the records."""


def read(ctx):
    if ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
