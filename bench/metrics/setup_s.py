"""Seconds from the start of the process until the window's load starts:
imports, weights, engine, and warming every program the traffic reaches."""


def read(run):
    return run.setup_s
