"""Process start to the first timed step: graph, partition, batcher,
compilation and the checked steps."""


def read(run):
    return run.setup_s
