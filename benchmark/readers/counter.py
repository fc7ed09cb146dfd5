"""A number the run counted or clocked once: args {"key": <counter>}."""


def read(summary, args):
    return summary["counters"].get(args["key"])
