"""100 x one counter over another: args {"part": <counter>, "whole": <counter>}."""


def read(summary, args):
    part = summary["counters"].get(args["part"])
    whole = summary["counters"].get(args["whole"])
    if part is None or not whole:
        return None
    return 100.0 * part / whole
