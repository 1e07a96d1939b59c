"""A Stage construction whose literal name IS in the registry
(ENGINE_STAGES), plus a wrapper-resolved fault point."""
from .runtime import Stage
from .stages import fault_point


class Loader:
    def __init__(self):
        self.stage = Stage("loader")

    def step(self):
        fault_point("loader", "read")
        self.stage.check("read")
