"""A Stage construction whose literal name is in NO registry —
no ENGINE_STAGES entry, no docs row, no fault-point constant (JL008)."""
from .runtime import Stage


def make():
    return Stage("mystery")
