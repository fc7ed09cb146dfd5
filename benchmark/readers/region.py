"""A region's own device time as a share of the traced window, from the
region table of the traced run (benchmark/xplane.py, `regions.rows`):
args {"region": <a name the program scopes>} summed over its phases,
{"phase": "forward" | "backward" | "recompute"} summed over the regions, or
both. Nothing without a table, or where it has no such row: a dense cell
has no `moe`."""


def read(summary, args):
    table = (summary.get("trace") or {}).get("regions")
    if not table:
        return None
    shares = [pct for region, phase, _seconds, pct, _ops in table["rows"]
              if args.get("region", region) == region
              and args.get("phase", phase) == phase]
    return sum(shares) if shares else None
