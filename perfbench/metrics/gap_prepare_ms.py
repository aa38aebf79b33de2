"""Mean milliseconds of device-idle time a gap, over the gaps between runs of
the dispatches' program that ``host_gap_ms`` averages, while the host was
under ``vault.build``, ``vault.stack`` or ``vault.launch``, building,
stacking, sending and enqueuing the next dispatch's inputs (device trace and
the engine's host spans)."""
from perfbench import scopes


def read(run):
    return scopes.gap_ms(run.trace, run.program_prefix).get("prepare")
