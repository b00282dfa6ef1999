"""Host seconds to build the graph, its partition (or read it from the
cache) and the batcher: `build_graph` plus `build_experiment`."""


def read(run):
    return run.setup_parts["graph_s"]
