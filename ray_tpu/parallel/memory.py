"""What a device's memory holds over a train step, as far as shapes say it.

Whoever builds the step (train/train_step.py:make_train_step) knows what the
model it differentiates cannot see: the devices, their memory limit, and the
bytes a device holds across the step (parameters and optimizer state, as their
shardings cut them). It says so around the trace (`told`), and a model that
can spend spare memory on less recomputation asks (`budget`) and reckons
(`reckoned_peak`, `most_kept`): models/gpt.py keeps the first rungs of a
layer's products through the per-layer remat where the reckoned peak allows,
five choices (gpt.LADDER: nothing more, its MLPs' `up x`, `gate x` too, what
a delta-rule or state-space mixer's filters read, what they write too).
Nothing here is set by a caller: no budget (a loss differentiated by hand, a platform
that reports no limit, as the CPU) means nothing more is kept.

The reckoning is a walk over the backward pass, not one sum. A layer's kept
values die as its weights' gradient is born, so with H_i the bytes layer i
keeps and G_i its gradient's, while layer j is differentiated a device holds

    state + the head's gradient + sum_{i >= j} G_i + sum_{i <= j} H_i
          + one layer's working set,

and the peak is the largest of those over j: the end of the forward pass
where the layers keep more than their gradients weigh, the end of the
backward pass where they keep less (granite: 0.17 GB kept against 0.30 GB of
gradient a layer; the chip reads state + the whole gradient, 12.65 GB, where
the plain sum says 13.9).
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
from typing import Any, Iterator, NamedTuple, Optional, Sequence

import jax
import numpy as np

from ray_tpu.util import metrics

logger = logging.getLogger(__name__)

# The share of a device's limit that the reckoned peak may reach: under the
# 89.0 % that nemotron3s_train_1chip reads (ledger, PR 62), the one cell in
# which XLA already rematerialises on its own, so that cell stays as it is.
CEILING = 0.88
# What a device holds that no shape of the step says: the program itself,
# its constants, the batch (the cells' bytes_in_use between two steps read
# 0.22-0.31 GB over their state: chip runs, PRs 57-62).
OVERHEAD = 400 * 2 ** 20


class Budget(NamedTuple):
    """limit: bytes a device may hold, the smallest `bytes_limit` of the
    step's devices; None where one reports none. state: bytes a device
    holds across the step (parameters, optimizer state, an accumulated
    gradient). share: the part of the parameters' bytes that a device holds
    (1 where nothing is sharded), which is also its part of a gradient."""
    limit: Optional[int]
    state: int
    share: float


_told: contextvars.ContextVar[Optional[Budget]] = contextvars.ContextVar(
    "memory_budget", default=None)


@contextlib.contextmanager
def told(budget: Budget) -> Iterator[None]:
    """While a step's loss is traced: what `budget()` answers."""
    token = _told.set(budget)
    try:
        yield
    finally:
        _told.reset(token)


def budget() -> Optional[Budget]:
    """The step's builder's word, or None where nobody gave one."""
    return _told.get()


def device_limit(devices: Sequence[Any]) -> Optional[int]:
    """The smallest `bytes_limit` among `devices`; None where a device
    reports no memory statistics (the CPU) or is described and not
    attached."""
    limits = []
    for device in devices:
        try:
            stats = device.memory_stats()
        except jax.errors.JaxRuntimeError:   # a described device
            stats = None
        if not stats or not stats.get("bytes_limit"):
            return None
        limits.append(int(stats["bytes_limit"]))
    return min(limits, default=None)


def tree_bytes(tree: Any, shardings: Any = None) -> int:
    """Bytes of tree's leaves (arrays, tracers or shapes): whole, or with
    `shardings` (a matching tree of shardings) what one device holds."""
    def size(leaf, sharding=None):
        shape = (leaf.shape if sharding is None
                 else sharding.shard_shape(leaf.shape))
        return int(np.prod(shape, dtype=np.int64)) * leaf.dtype.itemsize
    if shardings is None:
        return sum(map(size, jax.tree_util.tree_leaves(tree)))
    return sum(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(size, tree, shardings)))


def reckoned_peak(state: int, grads_before: int, grads: Sequence[int],
                  grads_after: int, held: Sequence[int], working: int,
                  head: int, passes: int = 1) -> int:
    """The module docstring's walk: bytes a device holds at the fullest
    moment of a step whose layer i keeps held[i] bytes through its remat
    and has grads[i] bytes of gradient. grads_before: of the parameters
    whose gradient is born before the layers' (the head's), grads_after:
    after them (the embedding's); working: what differentiating one layer
    takes beside what is kept; head: what the head takes after the forward
    pass. The moments: the head; each layer's turn; the end, all gradients
    and nothing else. passes > 1: a looped stack, which runs the layers
    that often over the same parameters. It keeps held[i] once a pass, and
    a layer's gradient is a sum over the passes that the loop carries: all
    of them are live from the backward of the last pass to the end of the
    first, so the fullest moment is the start of that backward, under every
    gradient and all that every pass keeps."""
    if passes > 1:
        kept, born = passes * sum(held), grads_before + sum(grads)
        return state + OVERHEAD + max(born + grads_after, kept + head,
                                      born + kept + working)
    most = max(grads_before + sum(grads) + grads_after, sum(held) + head)
    born, kept = grads_before + sum(grads), 0
    for g, h in zip(grads, held):
        kept += h
        most = max(most, born + kept + working)
        born -= g
    return state + OVERHEAD + most


def most_kept(limit: Optional[int], peaks: Sequence[int],
              ceiling: float = CEILING) -> int:
    """peaks[n]: the reckoned peak of the step that keeps its n-th choice
    (0: as it is), never falling with n -> the largest n whose peak is at
    or under ceiling x limit. 0 where there is no limit, and where the
    step is over the ceiling as it is. It never falls as the limit
    rises."""
    if limit is None:
        return 0
    return max([n for n, peak in enumerate(peaks)
                if peak <= ceiling * limit], default=0)


_KEPT = metrics.Gauge(
    "ray_tpu_train_mlp_kept_layers",
    "set when a train step is traced (parallel/memory.py): What=products, "
    "the rung of its products a layer keeps through the remat (0, 1: its "
    "MLP's up x, 2: gate x too, 3: what a delta-rule or state-space mixer's "
    "filters read, 4: what they write too), What=kept, the layers whose MLP "
    "keeps something, What=of, the layers that have such an MLP, "
    "What=mixer_kept, the layers whose mixer keeps something, What=bytes, "
    "what is kept so on a device, What=peak_bytes, the peak reckoned for a "
    "device at that choice",
    tag_keys=("What",))


def report(products: int, of: int, mixers: int, kept_bytes: int, peak: int,
           limit: Optional[int], passes: int = 1) -> None:
    """A traced step says what it keeps: the gauge
    ray_tpu_train_mlp_kept_layers (What=products | kept | of | mixer_kept |
    bytes | peak_bytes) in this process's registry, and a line of the log.
    products: the rung (0 .. 4); every one of the `of` layers that have an
    MLP keeps min(products, 2) of its products, and `mixers` layers' mixers
    what their filters read (rung 3) and write (4). A step that keeps
    nothing says so too. passes > 1: a looped stack, whose line says how
    often a layer's bytes are kept and that its gradients are held across
    the loop."""
    kept = of if products else 0
    for what, value in (("products", products), ("kept", kept), ("of", of),
                        ("mixer_kept", mixers), ("bytes", kept_bytes),
                        ("peak_bytes", peak)):
        _KEPT.set(value, tags={"What": what})
    logger.info(
        "mlp_kept_layers %d of %d, %d of an MLP's products each%s (%.2f GB a "
        "device kept; reckoned peak %.2f GB of %s)%s", kept, of,
        min(products, 2),
        "" if products < 3 else
        f", rung {products}: {mixers} layers' mixers keep what their filters "
        "read" + (" and write" if products > 3 else ""),
        kept_bytes / 1e9, peak / 1e9,
        "no limit reported" if limit is None else f"{limit / 1e9:.2f}",
        "" if passes == 1 else
        f"; a looped stack: a layer's kept bytes counted {passes} times, "
        "every layer's gradient live across the loop")
