"""Simulated deployment-hours completed per second of the window.

Hours are summed over every element (sweep point x seed) of every dispatch
in the window, each ``steps x step_hours``; the time is the window's whole
length on the host clock, up to the return of the last dispatch."""


def read(run):
    return run.hours / run.window_s
