"""A number of the traced stretch, as benchmark/xplane.py reduced it:
args {"key": "idle_pct" | "collective_pct" | ...}. Nothing without a trace."""


def read(summary, args):
    trace = summary.get("trace")
    return None if not trace else trace.get(args["key"])
