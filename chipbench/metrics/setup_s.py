"""Seconds from the start of the process to the window's opening:
imports, weights, engine, every compile or cache load, the lead-in."""


def read(run):
    return float(run.setup_s)
