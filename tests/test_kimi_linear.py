"""For a described v5e, at no chip time: kimilinear_train_1chip's whole step
(four delta-rule layers of 32 heads, a latent layer that rotates nothing, a
dense layer and four shares of 8 of 256 experts). The family's checks
against its reference are tests/test_kimi_linear_model.py's; the delta
rule's kernels at 8192 positions tests/test_linear_attention.py's, the
latent kernels' tests/test_latent_moe.py's."""

from helpers.described_chip import (  # noqa: F401 — fixtures and checks
    cell_step, test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_keeps_the_delta_rule_by_token,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step, v5e)
from helpers.families import family  # noqa: F401
from test_kimi_linear_model import FAMILY  # noqa: F401
