"""Where a value a driver was handed lives: in a store segment or not."""

import numpy as np


def in_attached_segment(buffer) -> bool:
    """True when `buffer` (anything with the buffer protocol) starts inside
    one of the shm segments this process's store client has attached: the
    value is a view into the node's object store, not a copy of it."""
    from ray_tpu._private import worker_api
    addr = np.frombuffer(buffer, dtype=np.uint8).__array_interface__[
        "data"][0]
    for shm in worker_api.peek_core().store._segments.values():
        seg = np.frombuffer(shm.buf, dtype=np.uint8)
        base = seg.__array_interface__["data"][0]
        if base <= addr < base + seg.nbytes:
            return True
    return False
