"""A kernel's share of its roofline: the least time one call could take on
the chip, the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, over
the seconds a call the trace measured (benchmark/xplane.py,
`regions.kernels`, every phase the kernel ran in). args {"kernel": <the
name= of the pallas_call>, "arithmetic": <module under benchmark/kernels/
whose function of that name gives (FLOPs, bytes) of one call on one chip>}.
Nothing without a table, where the kernel was not called, or where the
device has no peak in the table. A reading over 100 is a wrong count."""

import importlib


def read(summary, args):
    table = (summary.get("trace") or {}).get("regions")
    peak = summary.get("peak")
    if not table or peak is None:
        return None
    rows = [r for r in table["kernels"] if r[0] == args["kernel"]]
    calls = sum(r[3] for r in rows)
    if not calls:
        return None
    arithmetic = importlib.import_module(
        "benchmark.kernels." + args["arithmetic"])
    flops, hbm_bytes = getattr(arithmetic, args["kernel"])(
        summary["config"], summary["traffic"])
    least_s = max(flops / peak["bf16_flops"],
                  hbm_bytes / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / (sum(r[2] for r in rows) / calls)
