from .kernel import pack_int8_rows
from .ops import expand_frontier
from .ref import expand_frontier_1, expand_frontier_ref

__all__ = ["expand_frontier", "expand_frontier_1", "expand_frontier_ref",
           "pack_int8_rows"]
