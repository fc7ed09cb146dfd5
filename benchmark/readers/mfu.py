"""Model FLOP/s utilization of a train cell: tokens/s x the FLOPs a token
needs by the arithmetic of the configuration's family (6 N + 12 L d S for
gpt_dense) over chips x the device kind's bf16 peak. Arithmetic on the
end-to-end rate; recomputation is not counted. Nothing where the device has
no peak in the table (a rehearsal on the CPU)."""

from benchmark import model


def read(summary, args):
    rate = summary["counters"].get("tokens_per_s")
    if rate is None or summary.get("peak") is None:
        return None
    flops = model.family(summary["config"]).train_flops_per_token(
        summary["config"], summary["counters"]["seq"])
    return model.mfu_pct(rate, flops, summary["counters"]["chips"],
                         summary["device"]["kind"])
