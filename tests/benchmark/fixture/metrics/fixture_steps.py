"""Fixture metric: found by name from the fixture's BENCHMARK.json."""


def read(run):
    return run.steps
