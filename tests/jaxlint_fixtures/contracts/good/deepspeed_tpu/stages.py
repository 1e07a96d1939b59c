"""Stage plane of the good mini-project: every name is registered,
every fault point documented, drain order matches the docs fence."""

ENGINE_STAGES = (
    ("loader", "input"),
    ("writer", "output"),
)


def fault_point(stage, point):
    return (stage, point)


def wire(graph, loader, writer):
    graph.register("loader", close=loader.close, drain=loader.drain)
    graph.register("writer", close=writer.close, drain=writer.drain)


def tick():
    fault_point("loader", "read")
    fault_point("writer", "flush")
