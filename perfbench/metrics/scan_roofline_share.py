"""The scan step's share of its roofline, in percent: the least time a step
could take, its least bytes of group state (read and written once, split
over the chips) at the chip's HBM bandwidth from ``peaks.json``, over the
device time per step (as ``scan_step_us`` reads it). No published v5e peak
bounds the step's integer and compare work, so bytes are the bound."""


def read(run):
    if run.trace is None or not run.state_bytes_per_step:
        return None
    busy = run.trace.program_busy_s(run.program_prefix)
    if busy <= 0 or run.dispatches <= 0:
        return None
    step_s = busy / (run.dispatches * run.max_steps)
    least_s = run.state_bytes_per_step / run.chips / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
