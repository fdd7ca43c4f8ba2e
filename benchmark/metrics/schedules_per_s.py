"""Distinct schedules explored per second: each campaign's distinct
`sched_hash` count (the fuzzer's own dedup key), summed over the window's
campaigns, over the window's length."""


def read(run):
    return run["counts"]["schedules"] / run["elapsed"]
