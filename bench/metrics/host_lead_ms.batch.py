"""Mean over traced calls of the time from the call's start to its first
device op: host canonicalisation, cast, transfer and dispatch."""


def read(run):
    return run.trace.lead_tail_ms()[0]
