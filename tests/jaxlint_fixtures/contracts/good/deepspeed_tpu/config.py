import os

from . import constants as C


def load(d):
    return d.get(C.QUEUE_CAPACITY, C.QUEUE_CAPACITY_DEFAULT)


def pipeline_enabled():
    return os.getenv("DS_FIXTURE_PIPELINE", "1") == "1"
