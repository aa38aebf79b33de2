"""Device microseconds per scan step: the device time of the dispatches'
compiled program in the traced window, over the scan steps it ran
(``max_steps`` per dispatch), per chip (device trace)."""


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.program_busy_s(run.program_prefix)
    if busy <= 0 or run.dispatches <= 0:
        return None
    return 1e6 * busy / (run.dispatches * run.max_steps)
