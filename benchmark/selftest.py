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
5. The table of peaks and, through the family lookup, the FLOPs functions
   and the flash kernels' arithmetic; an unknown device kind is an error.
6. The join of a trace with a compiled step's HLO text on hand-made events:
   the region, kernel and collective tables, an op's own time in the
   breakdown, and the region and roofline readers on them, against shares
   worked out by hand; None for an absent region or kernel.
7. A new architecture is new files only: a throw-away family, configuration,
   traffic mix and cell in a directory of their own go through load_cell,
   the family lookup, the reference and the mfu reader, with no file under
   benchmark/ edited.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import estimators, model, run, traffic, xplane  # noqa: E402
from benchmark.kernels import flash_attention  # noqa: E402
from benchmark.readers import region, roofline  # noqa: E402

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
          f"top ops, by region and own time {want['top_ops']}")
    check("regions" not in got, "no region table without a compiled step")
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
    with open(os.path.join(HERE, "traffic", "train_b64_s1024_dp.json")) as f:
        dp = json.load(f)
    with open(os.path.join(HERE, "traffic",
                           "train_b32_s2048_tp_fsdp.json")) as f:
        tp_fsdp = json.load(f)
    family = model.family(gpt2s)
    check(family.__name__ == "benchmark.families.gpt_dense"
          and model.family(smol) is family,
          "a configuration without a family key is gpt_dense")
    check(family.param_count(gpt2s) == 190_532_352,
          "gpt2s in the repo's block: 190.5M parameters (762,129,408 B fp32)")
    check(family.param_count(smol) == 1_711_376_384,
          "smollm-1.7b: 1.711B parameters")
    check(family.train_flops_per_token(gpt2s, 1024) == 1256440320.0
          and family.train_flops_per_token(smol, 2048) == 11476217856.0,
          "6N + 12LdS = 1 256 440 320 FLOP a token at gpt2s, seq 1024, and "
          "11 476 217 856 at smollm-1.7b, seq 2048: the parent's, to the digit")
    check(family.forward_flops_per_token(gpt2s, 1024) * 3
          == family.train_flops_per_token(gpt2s, 1024),
          "a scoring forward is a third of it")
    check(abs(model.mfu_pct(48893, family.train_flops_per_token(gpt2s, 1024),
                            1, "TPU v5 lite") - 31.18) < 0.01,
          "48 893 tokens/s on one v5e is 31.2 % of 197 TFLOP/s")
    check(list(family.attention_call(gpt2s, dp).values())
          == [64, 12, 1024, 64]
          and list(family.attention_call(smol, tp_fsdp).values())
          == [16, 16, 2048, 64],
          "a flash call on one chip: [64,12,1024,64] and [16,16,2048,64]")
    fwd, fwd_bytes = flash_attention.flash_fwd(gpt2s, dp)
    dq, dq_bytes = flash_attention.flash_bwd_dq(gpt2s, dp)
    dkv, dkv_bytes = flash_attention.flash_bwd_dkv(gpt2s, dp)
    check(fwd == 4 * 64 * 12 * 1024 ** 2 * 64 / 2 == 103079215104.0,
          "flash_fwd at [64,12,1024,64]: 1.03e11 FLOPs a call")
    check(abs(dq + dkv - 2.5 * fwd) < 1.0 and abs(dq / dkv - 0.75) < 1e-12,
          "the two backward kernels: 2.5 x the forward together, 3 : 4")
    tensor = 64 * 12 * 1024 * 64 * 2
    check((fwd_bytes, dq_bytes, dkv_bytes)
          == (4 * tensor, 5 * tensor, 6 * tensor),
          "bytes: 4, 5 and 6 tensors of 100.7 MB, once each")
    check(flash_attention.flash_fwd(smol, tp_fsdp)[0]
          == 4 * 16 * 16 * 2048 ** 2 * 64 / 2,
          "flash_fwd at [16,16,2048,64]: 1.37e11 FLOPs a call")
    try:
        model.peak("TPU v9 imaginary")
    except KeyError:
        check(True, "an unknown device kind is an error")
    else:
        check(False, "an unknown device kind is an error")


MS = 1_000_000   # ns
STEP_HLO = """HloModule jit__step, is_scheduled=true

%body (p: f32[8]) -> f32[8] {
  %dot.1 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(_step)/loss_and_grad/jvp(head)/while/body/dot_general"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(_step)/loss_and_grad/jvp(mlp)/mul" stack_frame_id=3}
  %flash_fwd.2 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/loss_and_grad/transpose(jvp(loss_and_grad))/jvp()/checkpoint/rematted_computation/attn_core/flash_fwd/pallas_call"}
  %flash_fwd.12 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/loss_and_grad/jvp(attn_core)/flash_fwd/pallas_call"}
  %flash_bwd_dq.3 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_step)/loss_and_grad/transpose(jvp(loss_and_grad))/jvp()/checkpoint/attn_core/flash_bwd_dq/pallas_call"}
  %while.4 = f32[8]{0} while(%a), body=%body, metadata={op_name="jit(_step)/loss_and_grad/jvp(head)/while"}
  %all-reduce.5 = f32[8]{0} all-reduce(%a), metadata={op_name="jit(_step)/loss_and_grad/transpose(jvp(mlp))/dot_general"}
  %copy.6 = f32[8]{0} copy(%a)
  %copy.8 = f32[8]{0} copy(%flash_fwd.2)
  ROOT %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(_step)/optimizer/add"}
}
"""


def _event(name, opcode, start_ms, end_ms):
    return (f"%{name} = f32[8]{{0:T(8)}} {opcode}(%a)", start_ms * MS,
            end_ms * MS)


def hand_made_trace():
    """One chip, a window of 100 ms: ops back to back from 0 to 60 (the
    while spans its body's dot), a gap of 10 ms, three ops, a gap of 2 ms,
    one op, a gap of 3 ms, a copy of a kernel's output (no metadata of its
    own), and a kernel that runs on past the window's end."""
    return {"devices": {0: [
        _event("fusion.1", "fusion", 0, 10),
        _event("flash_fwd.12", "custom-call", 10, 20),
        _event("flash_bwd_dq.3", "custom-call", 20, 40),
        _event("while.4", "while", 40, 60), _event("dot.1", "dot", 45, 55),
        _event("all-reduce.5", "all-reduce", 70, 75),
        _event("copy.6", "copy", 75, 80),
        _event("fusion.7", "fusion", 82, 87),
        _event("copy.8", "copy", 90, 95),
        _event("flash_fwd.2", "custom-call", 95, 120)]},
        "host": [(xplane.WINDOW_SPAN, 0, 100 * MS),
                 ("host:dispatch", 0, 58 * MS),
                 ("host:report", 58 * MS, 72 * MS),
                 ("host:wait_step", 72 * MS, 100 * MS)]}


def close(got, want) -> bool:
    if isinstance(want, list):
        return len(got) == len(want) and all(map(close, got, want))
    if isinstance(want, float):
        return abs(got - want) <= 1e-12 * max(1.0, abs(want))
    return got == want


def test_regions() -> None:
    regions = ("attn_core", "mlp", "moe", "head", "loss_and_grad",
               "optimizer")
    kernels = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    got = xplane.reduce_trace(hand_made_trace(), hlo_text=STEP_HLO,
                              regions=regions, kernels=kernels)
    check(close([got["window_s"], got["busy_s"], got["idle_pct"],
                 got["collective_pct"]], [0.1, 0.085, 15.0, 5.0]),
          "hand-made trace: window 100 ms, busy 85, idle 15 %, collectives "
          "5 % (the same with and without the step's text)")
    table = got["regions"]
    # the last region on the path wins (attn_core under loss_and_grad); the
    # while's own time is 20 - 10 of its body's dot, both `head`; the copy
    # of a kernel's output inherits its region and phase, not its name
    check(close(table["rows"], [
        ["attn_core", "backward", 0.02, 20.0, 1],
        ["head", "forward", 0.02, 20.0, 2],
        ["mlp", "forward", 0.01, 10.0, 1],
        ["attn_core", "forward", 0.01, 10.0, 1],
        ["attn_core", "recompute", 0.01, 10.0, 2],
        ["mlp", "backward", 0.005, 5.0, 1],
        [xplane.UNATTRIBUTED, xplane.NO_PHASE, 0.005, 5.0, 1],
        ["optimizer", xplane.NO_PHASE, 0.005, 5.0, 1]]),
        "region rows: own time by (region, phase), summing to busy time, "
        "the largest first (equal ones in the trace's order)")
    check(close(table["kernels"], [
        ["flash_bwd_dq", "backward", 0.02, 1, 0.02],
        ["flash_fwd", "forward", 0.01, 1, 0.01],
        ["flash_fwd", "recompute", 0.005, 1, 0.005]]),
        "kernel rows: calls and seconds a call, the last clipped at the "
        "window's end; the copy is no call")
    check(close(table["collectives"], [["mlp", 0.005]]),
          "collective time by region")
    check(close(got["breakdown"]["device_ops"][:5], [
        ["attn_core/flash_bwd_dq.3", 0.02], ["mlp/fusion.1", 0.01],
        ["attn_core/flash_fwd.12", 0.01], ["head/while.4", 0.01],
        ["head/dot.1", 0.01]]),
        "breakdown: region/op with the op's own time, a scan (20 ms) and "
        "the matmul inside it (10 ms) each counted once")
    bare = xplane.reduce_trace(hand_made_trace())
    check(bare["breakdown"]["device_ops"][0]
          == ["unattributed/flash_bwd_dq.3", 0.02]
          and all(bare[k] == got[k] for k in bare if k != "breakdown"),
          "without the step's text every op is unattributed, the rest equal")
    second = [(n, a, b + 5 * MS) if "all-reduce" in n else (n, a, b)
              for n, a, b in hand_made_trace()["devices"][0]]
    two = xplane.reduce_trace(
        dict(hand_made_trace(), devices={0: hand_made_trace()["devices"][0],
                                         1: second}),
        hlo_text=STEP_HLO, regions=regions, kernels=kernels)
    check(close(two["regions"]["collectives"], [["mlp", 0.0075]])
          and close(two["collective_pct"], 7.5),
          "two chips: the median of two is their mean")

    def reads(reader, args, trace=got):
        return reader.read({"trace": trace, "peak": {
            "bf16_flops": 2e12, "hbm_bytes_per_s": 1e9}, "config": {},
            "traffic": {}}, args)
    check(close(reads(region, {"region": "attn_core"}), 40.0)
          and close(reads(region, {"region": "head"}), 20.0)
          and close(reads(region, {"region": xplane.UNATTRIBUTED}), 5.0),
          "region reader: attn_core 20 + 10 + 10 = 40 %, head 20 %, "
          "unattributed 5 % of the window")
    check(close(reads(region, {"phase": "recompute"}), 10.0)
          and close(reads(region, {"region": "mlp", "phase": "backward"}),
                    5.0),
          "region reader: recompute 10 % over all regions; one phase of one")
    check(reads(region, {"region": "moe"}) is None
          and reads(region, {"region": "mlp"}, bare) is None
          and reads(region, {"region": "mlp"}, None) is None,
          "region reader: None for an absent region, table or trace")
    # selftest_kernels below: 3e9 FLOP and 1e6 B for flash_fwd (1.5 ms at
    # 2 TFLOP/s, 1 ms at 1 GB/s), 1e9 FLOP and 4e6 B for flash_bwd_dq
    fake = types.ModuleType("benchmark.kernels.selftest_kernels")
    fake.flash_fwd = lambda config, mix: (3e9, 1e6)
    fake.flash_bwd_dq = lambda config, mix: (1e9, 4e6)
    fake.flash_bwd_dkv = lambda config, mix: (1e9, 1e6)
    sys.modules[fake.__name__] = fake
    args = {"arithmetic": "selftest_kernels"}
    check(close(reads(roofline, dict(args, kernel="flash_fwd")), 20.0),
          "roofline reader: flash_fwd 1.5 ms of FLOPs over 15 ms / 2 calls "
          "= 20 %, compute-bound")
    check(close(reads(roofline, dict(args, kernel="flash_bwd_dq")), 20.0),
          "roofline reader: flash_bwd_dq 4 ms of bytes over 20 ms = 20 %, "
          "memory-bound")
    check(reads(roofline, dict(args, kernel="flash_bwd_dkv")) is None
          and reads(roofline, dict(args, kernel="flash_fwd"), bare) is None,
          "roofline reader: None for a kernel that was not called, or "
          "without a table")


THROWAWAY_FAMILY = '''"""A bigram table: the smallest thing with the names a family has."""
import numpy as np


def param_count(config):
    return config["vocab_size"] ** 2


def train_flops_per_token(config, seq):
    return 6.0 * config["vocab_size"]


def reference_logprobs(params, tokens, config):
    z = params["table"][tokens[:, :-1]]
    z = z - np.log(np.exp(z).sum(-1, keepdims=True))
    return np.take_along_axis(z, tokens[:, 1:, None], -1)[..., 0]


def reference_loss(params, tokens, config):
    return -reference_logprobs(params, tokens, config).mean()
'''


def test_new_family() -> None:
    """What a model_config PR adds, in a directory of its own that becomes
    part of the `benchmark` namespace: nothing under benchmark/ is edited."""
    import numpy as np
    tree = tempfile.mkdtemp(prefix="selftest_family_")
    bench = os.path.join(tree, "benchmark")

    def write(path, content):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(content if isinstance(content, str)
                    else json.dumps(content))
    write(os.path.join(bench, "families", "throwaway.py"), THROWAWAY_FAMILY)
    write(os.path.join(bench, "configs", "bigram.json"),
          {"family": "throwaway", "vocab_size": 7})
    write(os.path.join(bench, "traffic", "bigram_train.json"),
          {"kind": "train", "global_batch": 2, "seq": 5})
    os.makedirs(os.path.join(bench, "metrics"))
    shutil.copy(os.path.join(HERE, "metrics", "train_mfu_pct.json"),
                os.path.join(bench, "metrics"))
    write(os.path.join(tree, "BENCHMARK.json"), {
        "configs": [{"name": "bigram",
                     "file": "benchmark/configs/bigram.json"}],
        "workloads": [{"name": "bigram_train", "config": "bigram",
                       "traffic": "bigram_train", "chips": 1}],
        "end_to_end": [],
        "per_layer": [{"name": "train_mfu_pct", "unit": "%",
                       "workloads": ["bigram_train"]}]})
    was = run.ROOT, run.HERE
    sys.path.insert(0, tree)     # benchmark.families gains the directory
    try:
        run.ROOT, run.HERE = tree, bench
        cell = run.load_cell("bigram_train", rehearsal=False)
        family = model.family(cell["config"])
        check(family.__name__ == "benchmark.families.throwaway"
              and family.__file__.startswith(tree),
              "a configuration's family key finds a module that no file "
              "under benchmark/ names")
        rng = np.random.default_rng(5)
        params = {"table": rng.normal(size=(7, 7))}
        tokens = rng.integers(0, 7, (2, 6))
        loss = family.reference_loss(params, tokens, cell["config"])
        by_hand = -np.mean([
            params["table"][a, b] - np.log(np.exp(params["table"][a]).sum())
            for row in tokens for a, b in zip(row[:-1], row[1:])])
        check(abs(loss - by_hand) < 1e-12,
              f"its reference answers ({loss:.6f})")
        values = run.per_layer_values(cell, {
            "counters": {"tokens_per_s": 1e12, "seq": 5, "chips": 1},
            "peak": model.peak("TPU v5 lite"), "config": cell["config"],
            "device": {"kind": "TPU v5 lite"}})
        check(close(values, {"train_mfu_pct": 100.0 * 1e12 * 42.0 / 197e12}),
              "and the mfu reader counts with its arithmetic "
              f"({values['train_mfu_pct']:.4f} %)")
    finally:
        run.ROOT, run.HERE = was
        sys.path.remove(tree)
        sys.modules.pop("benchmark.families.throwaway", None)
        shutil.rmtree(tree)


if __name__ == "__main__":
    for test in (test_throughput, test_steps_and_percentiles, test_traffic,
                 test_model, test_trace, test_regions, test_new_family):
        test()
    print("selftest passed")
