"""Mean over traced calls of the time from the call's last device op to
its return: fetch to the host and recovery."""


def read(run):
    return run.trace.lead_tail_ms()[1]
