"""For a described v5e, at no chip time: olmohybrid_train_1chip's whole step
(three gated delta-rule layers of 15 heads at key 96 / value 192 under a
decay a head, a full layer of 15 heads of 128 that rotates nothing, four
gated MLPs of 11008, the norm after each half). The family's checks against
its reference are tests/test_olmo_hybrid_model.py's; the delta rule's
kernels at these widths and head counts tests/test_linear_attention.py's."""

from helpers.described_chip import (  # noqa: F401 — fixtures and checks
    cell_step, test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step, v5e)
from helpers.families import family  # noqa: F401
from test_olmo_hybrid_model import FAMILY  # noqa: F401
