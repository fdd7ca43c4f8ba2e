"""Simulated events dispatched per second, summed over all seeds: the steps
every lane of every unit dispatched in the window, over the window's
length. A halted lane dispatches nothing and adds nothing."""


def read(run):
    return run["counts"]["events"] / run["elapsed"]
