"""A number the program counted itself, read from the metrics registry
(ray_tpu.util.metrics) of this process, where ray_tpu.init() and fit() ran:
args {"name": <ray_tpu_* metric>, "tags": {..}, "scale": <factor>}. A
gauge's value, a histogram's sum / count. Nothing where the registry has no
such row (a program that does not emit the metric, or a histogram that saw
no sample)."""

from ray_tpu.util import metrics


def read(summary, args):
    for row in metrics.snapshot():
        if row["name"] == args["name"] and row["tags"] == args.get("tags", {}):
            if row["type"] != "histogram":
                return row["value"] * args.get("scale", 1.0)
            if row["count"]:
                return row["sum"] / row["count"] * args.get("scale", 1.0)
    return None
