"""models/gpt.py's sparse block on one device, for the files that hold it
to the masked dense computation (tests/test_moe.py, tests/test_share_rows.py)."""


def experts(x, weights, idx, *matrices, held=None):
    """models/gpt.py's two halves of the sparse block as `_moe_block` joins
    them on one device: the slots' order from the routing decision
    (`_slot_order`: all that needs no row, so that a router ahead of the
    mixer can hand it across), then the experts over it. -> y, or with a
    share (y, [1] whether the bounded row space held the routing)."""
    from ray_tpu.models import gpt
    order = gpt._slot_order(idx, matrices[0].shape[0], held, x.dtype)
    out = gpt._experts(x, weights, order, *matrices, held=held)
    return out[0] if held is None else out
