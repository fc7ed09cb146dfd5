"""A statistic of readings taken inside the window:
args {"key": <series>, "stat": "median" | "p95" | "mean"}."""

from benchmark import estimators


def read(summary, args):
    values = summary["series"].get(args["key"])
    if not values:
        return None
    if args["stat"] == "mean":
        return sum(values) / len(values)
    return estimators.quantile(values, {"median": 0.5, "p95": 0.95}[args["stat"]])
