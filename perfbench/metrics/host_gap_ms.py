"""Mean milliseconds the device sat idle between consecutive runs of the
dispatches' compiled program, per chip (device trace). The breakdown's
``idle_gaps`` label each gap with the harness span that was open."""


def read(run):
    if run.trace is None:
        return None
    gaps = run.trace.gaps_between_runs(run.program_prefix)
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
