"""Stage plane of the bad mini-project: writer:flush is live in code
but undocumented (JL103), and the docs fence names a ghost stage."""

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
