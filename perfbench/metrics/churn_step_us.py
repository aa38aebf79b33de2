"""Device microseconds a scan step under the engine's ``vault.churn`` scope
(churn draws, burst thinning and the attack): the union of its operations'
intervals in the traced window, over ``dispatches x max_steps``, per chip,
the base of ``scan_step_us`` (device trace; each operation's scope from the
compiled program's HLO)."""
from perfbench import scopes


def read(run):
    return scopes.step_us(run.trace, (scopes.CHURN,), run.dispatches,
                          run.max_steps)
