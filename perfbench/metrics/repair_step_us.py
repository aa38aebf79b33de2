"""Device microseconds a scan step under the engine's ``vault.repair`` scope
(the repair cond): the union of its operations' intervals in the traced
window, over ``dispatches x max_steps``, per chip, the base of
``scan_step_us`` (device trace; each operation's scope from the compiled
program's HLO)."""
from perfbench import scopes


def read(run):
    return scopes.step_us(run.trace, (scopes.REPAIR,), run.dispatches,
                          run.max_steps)
