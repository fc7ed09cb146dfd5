"""For a described v5e, at no chip time: the state-space scan's two kernels at
granite4hm_train_1chip's call (64 heads on ONE B/C group, chunks of 256) and
the cell's whole step. The family's checks against its reference are
tests/test_hybrid_mixer_model.py's, the scan's own tests
tests/test_state_space.py's."""

import re

import pytest

from helpers.described_chip import (  # noqa: F401 — fixtures
    a_step_keeps_up_x, cell_step, v5e)
from helpers.families import family  # noqa: F401
from test_hybrid_mixer_model import FAMILY  # noqa: F401


def test_scan_compiles_at_8192_positions_of_64_heads_on_one_group(
        v5e, monkeypatch):
    """ops/state_space.py's two kernels at a Mamba layer of
    granite4hm_train_1chip, [1, 8192, 64, 64] on one group of 128 in chunks
    of 256, the cell's types: the block of heads follows the chunk
    (`_heads_a_step`: 16 heads a step, four blocks on the group); one
    Mosaic call each and no XLA loop beside them. B's and C's gradients
    leave `ssd_bwd` once a group in their type (PR 64: summed over the
    group's four blocks in VMEM), so no [64 / h, 8192, 128] float32 tensor
    is left for XLA to sum. What is kept between the two calls is the
    chunks' states, [64, 32, 64, 128] float32 = 67 MB a layer. The
    backward's VMEM temporaries did not grow over its parent's: the body
    that was `jax.vjp(_chunk)` took 31.96 MB of scoped VMEM here (a dozen
    [16, 256, 256] float32 tiles) and was refused under 30; the transpose
    written by hand, its tiles walked four heads at a time, takes 19.3 and
    compiles under 24."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import state_space
    from ray_tpu.ops.state_space import _heads_a_step, ssd

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=SingleDeviceSharding(v5e[0]))
    args = (shape((1, 8192, 64, 64)), shape((1, 8192, 64), jnp.float32),
            shape((64,), jnp.float32), shape((1, 8192, 1, 128)),
            shape((1, 8192, 1, 128)), shape((64,), jnp.float32))
    monkeypatch.setattr(state_space, "_BWD_PARAMS", pltpu.CompilerParams(
        dimension_semantics=state_space._BWD_PARAMS.dimension_semantics,
        vmem_limit_bytes=24 << 20))
    state_space._make_ssd_fn.cache_clear()
    try:
        compiled = jax.jit(jax.grad(
            lambda *a: jnp.sum(ssd(*a, chunk=256, interpret=False).astype(
                jnp.float32)),
            argnums=tuple(range(6)))).lower(*args).compile()
    finally:
        state_space._make_ssd_fn.cache_clear()
    text = compiled.as_text()
    calls = re.findall(r"%(\S*ssd_(?:fwd|bwd)\S*) = .*custom-call\(", text)
    assert len(calls) == 2 and "fwd" in calls[0] and "bwd" in calls[1], calls
    assert text.count("tpu_custom_call") == 2 and " while(" not in text
    blocks = 64 // _heads_a_step(256, 64)
    assert blocks == 4 and f"f32[{blocks},8192,128]" not in text
    assert "reduce-window" in text      # `chunk_log_decay`, forward
    states = 64 * 32 * 64 * 128 * 4
    # 73.7 MB when this was written (105.2 with dB and dC a block of heads)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2 * states


from helpers.described_chip import (  # noqa: E402,F401
    test_the_cells_that_were_there_lower_to_the_same_step,
    test_cell_step_compiles_under_the_chips_memory,
    test_cell_step_makes_a_heads_dw_where_its_logits_are,
    test_the_new_scopes_are_regions_and_reach_the_compiled_step)


@pytest.mark.parametrize("name,layers", [("granite-4.0-h-micro", 10)])
def test_a_step_keeps_up_x_under_the_chips_memory(cell_step,  # noqa: F811
                                                  name, layers):
    """The file's one compiled step is the one the chip runs, as every
    family's is, so it is read here too (until PR 70 a case of
    tests/test_mlp_kept.py, which compiled the cell a second time)."""
    assert name == FAMILY.cell
    a_step_keeps_up_x(cell_step, layers)
