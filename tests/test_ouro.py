"""For a described v5e, at no chip time: ouro26_train_1chip's whole step (12
dense layers of 16 heads of 128 under four norms a layer, run four times a
step over the same weights as ONE loop body, four passes of the head in one
call, the exit gate), compiled once, as the chip runs it (the builder reads
a v5e's memory limit). The family's checks against its reference are
tests/test_ouro_model.py's."""

from helpers.described_chip import (  # noqa: F401 — fixtures and checks
    cell_step, kernel_ops, test_cell_step_compiles_under_the_chips_memory,
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step, v5e)
from helpers.families import family  # noqa: F401
from test_ouro_model import FAMILY  # noqa: F401


def test_the_loop_is_one_body_and_the_head_one_call(cell_step):
    """The compiled step's entry computation holds three whiles (the passes
    forward, the head's two chunks of the passes' rows, the passes backward)
    and none of the flash kernels: every one stands in a loop's body, once a
    layer; nothing is made again by the compiler under memory pressure
    (`.remat`); and the step told its builder what a loop keeps: each
    layer's bytes four times, nothing more, under a reckoned peak that
    includes every layer's gradient."""
    import re
    text = cell_step.text
    entry = text[text.index("\nENTRY "):]
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert not kernel_ops(entry, kernel), kernel
    assert len(re.findall(r" while\(", entry)) == 3
    assert not re.findall(r"%[\w.\-]*\.remat[\w.\-]* = ", text)
    products, of, _mixers, kept_bytes, peak, limit, passes = cell_step.kept
    assert (passes, products, of, kept_bytes) == (4, 0, 8, 0)
    state = cell_step.memory.argument_size_in_bytes
    assert state + 8 * 51_388_416 * 4 < peak < 0.88 * limit
