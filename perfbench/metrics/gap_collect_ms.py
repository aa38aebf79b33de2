"""Mean milliseconds of device-idle time a gap, over the gaps between runs of
the dispatches' program that ``host_gap_ms`` averages, while the host was
under ``vault.fetch`` or ``vault.gather``, copying the last dispatch's
outputs to the host and joining them (device trace and the engine's host
spans)."""
from perfbench import scopes


def read(run):
    return scopes.gap_ms(run.trace, run.program_prefix).get("collect")
