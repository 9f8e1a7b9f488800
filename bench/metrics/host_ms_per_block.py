"""Host time a block of the scan loop spends outside its stats pull: the
mean per block of each ``fed.host.*`` span of ``FedSAEServer._run_scan``
other than ``fed.host.pull`` (dispatch, eval, records, checkpoint), summed
over the phases.  Read from the host events of the traced window; a span
cut by the trace's start or stop is not in the trace, so each phase's mean
is over its whole spans.  Layer: the host loop, whose work between pulls
keeps the device idle.  Silent for a program without the spans."""

PREFIX = "fed.host."
PULL = "fed.host.pull"


def read(ctx):
    phases = {}
    for name, start, end in ctx.trace.host:
        if name.startswith(PREFIX) and name != PULL:
            total, n = phases.get(name, (0, 0))
            phases[name] = (total + end - start, n + 1)
    if not phases:
        return None
    return 1e-6 * sum(total / n for total, n in phases.values())
