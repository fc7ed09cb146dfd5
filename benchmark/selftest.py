"""Self-test of the yardstick: runs on the CPU, opens no JAX backend.

    python3 benchmark/selftest.py

1. The closed-loop throughput estimator on synthetic completion times like
   the ones that refused PR 22 (bursts of 8 every 281 ms, 32 callers): with
   the window's edges shifted through a whole burst period the rate over
   whole turns must move by under 0.1 % where completions / window moves
   by the 2 % the ledger saw; a stall of a second must lower it by the
   work it cost, and leave the median turn rate (per-layer) alone.
2. The train rate over all whole steps, a slow step included, beside the
   step-time median; the percentiles, sample counts asserted.
3. The traffic generator: every seed gets the same lengths and gaps.
4. The trace reduction on the recorded v5e trace in fixtures/, against
   values worked out by hand from its events (fixtures/expected.json).
5. The table of peaks and the FLOPs functions; an unknown device kind is an
   error.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import estimators, model, traffic, xplane  # noqa: E402

BURST, PERIOD, CLIENTS, WINDOW = 8, 0.281, 32, 15.0


def completions(offset: float, stall_at: float = -1.0, stall_s: float = 0.0):
    """Bursts of BURST completions every PERIOD from `offset`, each request
    of a burst a fraction of a millisecond after the one before; an optional
    stall delays everything after `stall_at`."""
    out, t = [], offset
    while t < WINDOW + 2.0:
        shift = stall_s if stall_at >= 0 and t >= stall_at else 0.0
        out.extend(t + shift + 0.0002 * k for k in range(BURST))
        t += PERIOD
    return out


def spread(values):
    return (max(values) - min(values)) / estimators.median(values)


def check(cond: bool, what: str) -> None:
    print(("ok   " if cond else "FAIL ") + what)
    if not cond:
        raise SystemExit(1)


def test_throughput() -> None:
    truth = BURST / PERIOD
    runs = [[t for t in completions(-PERIOD * k / 16) if 0 <= t <= WINDOW]
            for k in range(16)]
    stalled = [t for t in completions(0.0, stall_at=7.0, stall_s=1.0)
               if 0 <= t <= WINDOW]
    rate = [estimators.loop_rate(r, CLIENTS) for r in runs]
    count = [estimators.count_rate(r, 0.0, WINDOW) for r in runs]
    check(all(n == 13 for _r, n in rate), "each run holds 13 whole turns")
    check(spread([r for r, _n in rate]) < 0.001,
          f"the rate over whole turns moves "
          f"{100 * spread([r for r, _n in rate]):.4f} % over edge shifts "
          f"(truth {truth:.3f}, read {rate[0][0]:.3f})")
    check(abs(rate[0][0] - truth) / truth < 0.001, "and reads the true rate")
    check(0.015 < spread(count) < 0.025,
          f"completions/window moves {100 * spread(count):.2f} % over the "
          "edge shifts alone")
    lost = 1.0 - estimators.loop_rate(stalled, CLIENTS)[0] / truth
    check(0.06 < lost < 0.08, f"a 1 s stall in 15 s lowers the rate by "
          f"{100 * lost:.2f} %: the work it cost")
    turn = estimators.median_turn_rate(stalled, CLIENTS)
    check(abs(turn - truth) / truth < 0.001,
          f"and leaves the median turn rate alone ({turn:.3f})")
    stall = estimators.stall_share(stalled, PERIOD, 0.0, WINDOW)
    check(0.08 < stall < 0.09, f"the stall is seen: {100 * stall:.1f} % of "
          "the window")
    check(estimators.stall_share(runs[0], PERIOD, 0.0, WINDOW) == 0.0,
          "and no stall where there is none")


def test_steps_and_percentiles() -> None:
    steps, t = [], 0.013
    for i in range(12):
        dispatch = t + 0.004
        t = dispatch + (2.0 if i == 5 else 1.339)   # one slow step
        steps.append((dispatch, t))
    times = estimators.step_times(steps, 0.0, 15.0)
    rate, n = estimators.step_rate(steps, 0.0, 15.0)
    check(n == 9 and abs(estimators.median(times) - 1.343) < 1e-9,
          f"step time: median {estimators.median(times):.3f} s of {n} "
          "readings, the step over the edge left out")
    check(abs(rate - 9 / (8 * 1.343 + 2.004)) < 1e-12,
          f"step rate {rate:.4f}/s counts the slow step: 9 steps over "
          "the time from the first being done to the last")
    gap = estimators.host_gap_share(steps, 0.0, 15.0)
    check(abs(gap - 9 * 0.004 / (steps[9][1] - steps[0][1])) < 1e-12,
          f"host gap {100 * gap:.3f} % of the stepped time")
    values = [float(v) for v in range(1, 401)]
    check(estimators.quantile(values, 0.5) == 200.5
          and abs(estimators.quantile(values, 0.95) - 380.05) < 1e-9,
          "p50 and p95 of 1..400 interpolate between order statistics")
    check(estimators.samples_beyond(400, 0.95) == 20,
          "400 readings leave 20 beyond the p95")


def test_traffic() -> None:
    mix = {"lengths": [{"share": 0.8, "dist": "loguniform", "min": 16,
                        "max": 128},
                       {"share": 0.2, "dist": "uniform", "min": 512,
                        "max": 1024}],
           "pool": 200, "rate_per_s": 50.0, "arrivals": {"dist": "poisson"}}
    a = traffic.prompts(mix, 1000, 1)
    b = traffic.prompts(mix, 1000, 2 ** 31 + 11)
    check(sorted(map(len, a)) == sorted(map(len, b)) and a != b,
          "two seeds: the same lengths, another order, other tokens")
    check(sum(1 for p in a if len(p) <= 128) == 160
          and min(map(len, a)) >= 16 and max(map(len, a)) <= 1024,
          "a mixture keeps its shares and its limits")
    da, db = traffic.due_times(mix, 20.0, 1), traffic.due_times(mix, 20.0, 2)
    check(len(da) == 1000 and abs(da[-1] - 20.0) < 1e-9
          and abs(db[-1] - 20.0) < 1e-9 and da != db,
          "1000 arrivals end at 20 s for every seed, in another order")
    gaps = sorted(y - x for x, y in zip([0.0] + da, da))
    cv = (sum((g - 0.02) ** 2 for g in gaps) / len(gaps)) ** 0.5 / 0.02
    check(0.95 < cv < 1.02, f"Poisson gaps: coefficient of variation {cv:.3f}")


def test_trace() -> None:
    with open(os.path.join(HERE, "fixtures", "expected.json")) as f:
        want = json.load(f)
    got = xplane.reduce_trace(xplane.load(
        os.path.join(HERE, "fixtures", "trace.xplane.pb")))
    for key in ("window_s", "busy_s", "idle_pct", "collective_pct"):
        check(abs(got[key] - want[key]) <= 1e-9 * max(1.0, abs(want[key])),
              f"trace {key} = {got[key]!r}")

    def same(a, b):
        return len(a) == len(b) and all(
            x[0] == y[0] and abs(x[1] - y[1]) < 1e-12 for x, y in zip(a, b))
    check(same(got["breakdown"]["device_ops"][:2], want["top_ops"]),
          f"top ops {want['top_ops']}")
    check(same(got["breakdown"]["idle_gaps"], want["idle_gaps"]),
          f"idle gaps by host span {want['idle_gaps']}")
    names = [
        "%all-gather-start.3 = (bf16[4]{0}, bf16[8]{0:T(8,128)(2,1)}) "
        "all-gather-start(bf16[4]{0} %x), dimensions={0}",
        "%all-reduce.12 = f32[8]{0:T(256)} all-reduce(f32[8]{0} %y)",
        "%fusion.9 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8] %z)"]
    check([xplane.is_collective(n) for n in names] == [True, True, False],
          "collectives told from fusions by opcode")


def test_model() -> None:
    with open(os.path.join(HERE, "configs", "gpt2s.json")) as f:
        gpt2s = json.load(f)
    with open(os.path.join(HERE, "configs", "smollm-1.7b.json")) as f:
        smol = json.load(f)
    check(model.param_count(gpt2s) == 190_532_352,
          "gpt2s in the repo's block: 190.5M parameters (762,129,408 B fp32)")
    check(model.param_count(smol) == 1_711_376_384,
          "smollm-1.7b: 1.711B parameters")
    check(abs(model.train_flops_per_token(gpt2s, 1024) - 1.2564e9) < 1e5,
          "gpt2s: 6N + 12LdS = 1.256 GFLOP a token at seq 1024")
    check(abs(model.mfu_pct(48893, model.train_flops_per_token(gpt2s, 1024),
                            1, "TPU v5 lite") - 31.18) < 0.01,
          "48 893 tokens/s on one v5e is 31.2 % of 197 TFLOP/s")
    try:
        model.peak("TPU v9 imaginary")
    except KeyError:
        check(True, "an unknown device kind is an error")
    else:
        check(False, "an unknown device kind is an error")


if __name__ == "__main__":
    for test in (test_throughput, test_steps_and_percentiles, test_traffic,
                 test_model, test_trace):
        test()
    print("selftest passed")
