"""Busy device time in the traced window per model step executed there,
in milliseconds (steps are the trace's module executions)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.executions:
        return None
    return 1e3 * t.busy_s * t.devices / t.executions
