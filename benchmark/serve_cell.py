"""A serve cell: one replica behind the HTTP proxy (serve.run), driven by a
load generator in this process: one thread, one asyncio loop, a connection a
request (the proxy closes each). The replica class below is the application;
the system under test is what lies between the client's socket and the
replica's batch: proxy, handle, replica loop, @serve.batch.

A scoring request carries a prompt's token ids; the answer is the
log-probability of every token after the first given the tokens before it:
one forward, no decode (the repo has no KV cache).
"""

from __future__ import annotations

import asyncio
import gc
import json
import math
import socket
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmark import estimators, traffic

ROUTE = "/score"
REQUEST_TIMEOUT_S = 60.0


def make_replica_class(serve_params: Dict[str, Any]):
    """The replica class for one deployment: @serve.batch's parameters are
    fixed when the class is made."""
    import queue
    import threading

    from ray_tpu import serve

    class ScoreReplica:
        """Holds seeded weights on the chip in the type they are served in
        (bf16). All JAX work runs on one thread of its own: the replica's
        loop keeps answering health checks while that thread opens the chip
        and compiles (a constructor that did so would be killed at the
        controller's 60 s grace), and forwards run in arrival order."""

        def __init__(self, spec: Dict[str, Any]):
            self._spec = spec
            self._jobs: "queue.Queue" = queue.Queue()
            self._m: Optional[Dict[str, Any]] = None
            self._batches: List[Dict[str, float]] = []
            threading.Thread(target=self._device_loop, daemon=True,
                             name="device").start()

        async def __call__(self, request):
            entered = time.perf_counter()
            msg = request.json()
            if "tokens" in msg:
                return await self._score(msg["tokens"], entered)
            return await self._submit(("control", msg))

        @serve.batch(max_batch_size=serve_params["max_batch_size"],
                     batch_wait_timeout_s=serve_params["batch_wait_timeout_s"])
        async def _score(self, prompts, entered):
            return await self._submit(("score", prompts, entered))

        def _submit(self, job):
            loop = asyncio.get_running_loop()
            future = loop.create_future()
            self._jobs.put((job, loop, future))
            return future

        def _device_loop(self):
            import jax

            def settle(future, fn, value):
                if not future.done():
                    fn(value)
            while True:
                with jax.profiler.TraceAnnotation("host:batch_wait"):
                    job, loop, future = self._jobs.get()
                try:
                    result = (self._forward_batch(*job[1:])
                              if job[0] == "score" else self._control(job[1]))
                except Exception as e:  # the request's boundary: it fails
                    loop.call_soon_threadsafe(settle, future,
                                              future.set_exception, e)
                else:
                    loop.call_soon_threadsafe(settle, future,
                                              future.set_result, result)

        # -- everything below runs on the device thread --

        def _load(self) -> Dict[str, Any]:
            import jax
            import numpy as np

            from benchmark import model, worker

            spec = self._spec
            timeline = worker.Timeline("load_entered_wall")
            device = worker.open_device(spec["platform"], 1)
            timeline.mark("device_open")
            watch = worker.CompileWatch()
            family = model.family(spec["model"])
            served = family.program(spec["model"], serving=True)
            params = jax.jit(served.init)(
                jax.random.PRNGKey(spec["seed"] % 2 ** 32))
            jax.block_until_ready(params)
            timeline.mark("weights_ready")
            # the one shape every forward runs: max_batch_size x pad_to
            blank = np.zeros((spec["serve"]["max_batch_size"],
                              spec["serve"]["pad_to"]), np.int32)
            program = jax.jit(served.score).lower(params, blank).compile()
            program(params, blank).block_until_ready()
            timeline.mark("forward_warm")
            self._m = {"device": device, "watch": watch, "params": params,
                       "score": program, "reference": jax.jit(
                           lambda p, t: family.reference_logprobs(
                               p, t, spec["model"]))}
            return {"device": device, "setup": watch.snapshot(),
                    "timeline": timeline.marks}

        def _forward_batch(self, prompts, entered):
            import jax
            import numpy as np
            m, serve_p = self._m, self._spec["serve"]
            started = time.perf_counter()
            with jax.profiler.TraceAnnotation("host:batch_prepare"):
                tokens = np.zeros((serve_p["max_batch_size"],
                                   serve_p["pad_to"]), np.int32)
                for i, prompt in enumerate(prompts):
                    if not 2 <= len(prompt) <= serve_p["pad_to"]:
                        raise ValueError(
                            f"prompt of {len(prompt)} tokens, need 2.."
                            f"{serve_p['pad_to']}")
                    tokens[i, :len(prompt)] = prompt
            with jax.profiler.TraceAnnotation("host:forward"):
                t0 = time.perf_counter()
                out = m["score"](m["params"], tokens)
                out.block_until_ready()
                t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("host:reply"):
                host = np.asarray(out)
                forward_ms = (t1 - t0) * 1e3
                answers = [
                    {"logprobs": host[i, :len(p) - 1].tolist(),
                     "server": {"queue_ms": (started - entered[i]) * 1e3,
                                "forward_ms": forward_ms,
                                "batch": len(prompts)}}
                    for i, p in enumerate(prompts)]
            self._batches.append({
                "end": t1, "forward_ms": forward_ms, "requests": len(prompts),
                "tokens": sum(len(p) for p in prompts),
                "padded_tokens": tokens.size})
            return answers

        def _control(self, msg: Dict[str, Any]):
            import jax
            import numpy as np

            from benchmark import worker
            op = msg["op"]
            if op == "load":
                return self._load()
            m = self._m
            if op == "mark":    # the window starts: compiles count from here
                m["mark"] = m["watch"].snapshot()
                return {}
            if op == "stats":
                now = m["watch"].snapshot()
                return {"batches": self._batches,
                        "compiles": now["compiles"] - m["mark"]["compiles"],
                        "memory": worker.memory_peak(1, m["score"])}
            if op == "trace_start":
                m["tracer"] = worker.Tracer(msg["dir"], self._spec["platform"])
                m["tracer"].start()
                return {}
            if op == "trace_stop":
                return m.pop("tracer").stop()
            if op == "reference":
                # the family's plain reference on the served weights:
                # float32, full precision, a few prompts at a call
                pad_to, rows = self._spec["serve"]["pad_to"], msg["rows"]
                answers = []
                for at in range(0, len(msg["prompts"]), rows):
                    chunk = msg["prompts"][at:at + rows]
                    tokens = np.zeros((rows, pad_to), np.int32)
                    for i, p in enumerate(chunk):
                        tokens[i, :len(p)] = p
                    with jax.default_matmul_precision("highest"):
                        out = np.asarray(m["reference"](m["params"], tokens))
                    answers += [out[i, :len(p) - 1].tolist()
                                for i, p in enumerate(chunk)]
                return {"logprobs": answers}
            raise ValueError(f"unknown op {op!r}")

    return ScoreReplica


# ---------------------------------------------------------------------------
# The load generator
# ---------------------------------------------------------------------------

async def post(port: int, body: bytes) -> Any:
    """One request over a connection of its own; returns the parsed answer
    or raises."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(b"POST %s HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\nConnection: close\r\n\r\n"
                     % (ROUTE.encode(), len(body)) + body)
        await writer.drain()
        raw = await reader.read()
    finally:
        writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = head.split(b" ", 2)[1:2]
    if status != [b"200"]:
        raise RuntimeError(f"HTTP {head[:80]!r} {payload[:200]!r}")
    return json.loads(payload)


def control(port: int, msg: Dict[str, Any], timeout: float = 1100.0) -> Any:
    return asyncio.run(asyncio.wait_for(
        post(port, json.dumps(msg).encode()), timeout))


class Load:
    """Sends a mix's requests and keeps, for each, when it was due, sent and
    answered, what the replica said about it, and whether it failed."""

    def __init__(self, port: int, mix: Dict[str, Any], vocab: int, seed: int):
        self.port, self.mix = port, mix
        self.prompts = traffic.prompts(mix, vocab, seed)
        self.bodies = [json.dumps({"tokens": p}).encode()
                       for p in self.prompts]
        self.records: List[Dict[str, Any]] = []
        self.answers: Dict[int, List[float]] = {}   # the sample to check

    async def _one(self, index: int, due: float) -> None:
        record = {"prompt": index, "due": due, "sent": time.perf_counter(),
                  "done": None, "ok": False}
        self.records.append(record)
        try:
            answer = await asyncio.wait_for(
                post(self.port, self.bodies[index]), REQUEST_TIMEOUT_S)
            logprobs = answer["logprobs"]
            record["ok"] = (len(logprobs) == len(self.prompts[index]) - 1
                            and math.isfinite(sum(logprobs)))
            record["server"] = answer["server"]
            if index < self.mix["check_prompts"]:
                self.answers.setdefault(index, logprobs)
        except Exception as e:  # the request failed: counted, not raised
            record["error"] = f"{type(e).__name__}: {e}"[:200]
        record["done"] = time.perf_counter()

    async def closed(self, start: float, end: float) -> None:
        """`clients` callers from `start`; each sends its next request when
        the last is answered, and none starts a request after `end`."""
        clients, pool = self.mix["clients"], len(self.prompts)

        async def caller(k: int) -> None:
            j = k
            while time.perf_counter() < end:
                await self._one(j % pool, time.perf_counter())
                j += clients
        await asyncio.sleep(max(start - time.perf_counter(), 0))
        await asyncio.gather(*(caller(k) for k in range(clients)))

    async def open(self, start: float, dues: List[float]) -> None:
        """One request at each due time after `start`, whatever the server
        does; a request is timed from when it was due."""
        pool, tasks = len(self.prompts), []
        for i, due in enumerate(dues):
            delay = start + due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(
                self._one(i % pool, start + due)))
        await asyncio.gather(*tasks)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def deploy(cell: Dict[str, Any], seed: int) -> Tuple[int, Dict[str, Any]]:
    """serve.run the cell's deployment and load it; returns the proxy's port
    and what the replica said of its device and set-up."""
    from ray_tpu import serve
    mix = cell["traffic"]
    port = free_port()
    serve.start(http_options=serve.HTTPOptions(port=port))
    app = serve.deployment(
        make_replica_class(mix["serve"]), name="score",
        ray_actor_options={"num_tpus": 1}).bind(
            {"model": cell["config"], "serve": mix["serve"], "seed": seed,
             "platform": cell["platform"]})
    serve.run(app, name="bench", route_prefix=ROUTE)
    return port, control(port, {"op": "load"})


def _sender(load: Load, mix: Dict[str, Any], begin: float, seconds: float,
            seed: int):
    """The mix's traffic from `begin` for `seconds`."""
    if mix["loop"] == "closed":
        return load.closed(begin, begin + seconds)
    return load.open(begin, traffic.due_times(mix, seconds, seed))


def measure(port: int, mix: Dict[str, Any], vocab: int, seed: int,
            seconds: float) -> Dict[str, Any]:
    """Warm the loop up for mix['warmup_s'], then measure for `seconds`.
    Returns the load's records and the replica's own."""
    load = Load(port, mix, vocab, seed)
    warm = mix["warmup_s"]
    gc.collect()
    gc.freeze()      # the generator's own pauses are not the server's
    gc.disable()

    async def drive():
        begin = time.perf_counter() + 0.05
        start = begin + warm
        task = asyncio.ensure_future(
            _sender(load, mix, begin, warm + seconds, seed))
        await asyncio.sleep(max(start - time.perf_counter(), 0))
        wall = time.time()
        await post(port, json.dumps({"op": "mark"}).encode())
        await task
        return start, wall
    start, wall = asyncio.run(drive())
    gc.enable()
    gc.unfreeze()
    return {"start": start, "end": start + seconds, "wall_start": wall,
            "records": load.records, "load": load,
            "replica": control(port, {"op": "stats"})}


def trace_stretch(port: int, mix: Dict[str, Any], vocab: int, seed: int,
                  trace_dir: str) -> Dict[str, Any]:
    """A few seconds of the same traffic with the profiler on, after the
    measured window."""
    load = Load(port, mix, vocab, seed + 7)
    seconds = mix["trace_s"]

    async def drive():
        begin = time.perf_counter() + 0.05
        task = asyncio.ensure_future(
            _sender(load, mix, begin, 1.0 + seconds, seed + 7))
        await asyncio.sleep(1.0)     # the loop is steady again
        await post(port, json.dumps({"op": "trace_start",
                                     "dir": trace_dir}).encode())
        await asyncio.sleep(seconds)
        reduced = await post(port, json.dumps({"op": "trace_stop"}).encode())
        await task
        return reduced
    return asyncio.run(drive())


def check_answers(port: int, load: Load, mix: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """The window's answers to the pool's first `check_prompts` prompts
    against the family's plain reference on the same weights. The tolerances
    and their reason are in the mix's file."""
    indices = sorted(load.answers)
    if not indices:
        return {"ok": False, "why": "no answer to check"}
    reference = control(port, {
        "op": "reference", "rows": mix["reference_rows"],
        "prompts": [load.prompts[i] for i in indices]})["logprobs"]
    worst, total, n = 0.0, 0.0, 0
    for i, ref in zip(indices, reference):
        for a, b in zip(load.answers[i], ref):
            worst = max(worst, abs(a - b))
            total += abs(a - b)
            n += 1
    return {"prompts": len(indices), "tokens": n, "max_abs": worst,
            "mean_abs": total / n,
            "ok": bool(worst <= mix["logprob_max_abs_tol"]
                       and total / n <= mix["logprob_mean_abs_tol"])}


def run(cell: Dict[str, Any], args) -> Dict[str, Any]:
    mix, vocab = cell["traffic"], cell["config"]["vocab_size"]
    port, loaded = deploy(cell, args.seed)
    facts = {"device": loaded["device"], "setup": loaded["setup"],
             "timeline": loaded["timeline"]}
    window = measure(port, mix, vocab, args.seed, args.seconds)
    load = window.pop("load")
    facts["window"] = window
    facts["memory"] = window["replica"]["memory"]
    if args.trace:
        facts["trace"] = trace_stretch(port, mix, vocab, args.seed,
                                       cell["trace_dir"])
    facts["check"] = check_answers(port, load, mix)
    return facts


def summarize(cell: Dict[str, Any], facts: Dict[str, Any]) -> Dict[str, Any]:
    """Readings of the window -> end-to-end metrics, and the counters and
    series the per-layer readers take theirs from. The replica's clock and
    the client's are the same monotonic clock of one host."""
    mix, w = cell["traffic"], facts["window"]
    start, end = w["start"], w["end"]
    due = [r for r in w["records"] if start <= r["due"] <= end]
    good = [r for r in due if r["ok"]]
    done_in = sorted(r["done"] for r in w["records"]
                     if r["ok"] and start <= r["done"] <= end)
    batches = [b for b in w["replica"]["batches"] if start <= b["end"] <= end]
    e2e: Dict[str, Optional[float]] = {}
    sizes = [b["requests"] for b in batches]
    info: Dict[str, Any] = {"requests_due_in_window": len(due),
                            "completions_in_window": len(done_in),
                            "batches_in_window": len(batches),
                            "batches_by_requests": {
                                str(n): sizes.count(n) for n in sorted(set(sizes))}}
    counters: Dict[str, Any] = {}
    series: Dict[str, List[float]] = {}
    late_ok = True
    if mix["loop"] == "closed":
        rate, turns = estimators.loop_rate(done_in, mix["clients"])
        e2e["serve_requests_per_s"] = rate
        counters["median_turn_per_s"] = estimators.median_turn_rate(
            done_in, mix["clients"])
        info.update(whole_turns=turns,
                    median_turn_per_s=counters["median_turn_per_s"],
                    count_over_window_per_s=estimators.count_rate(
                        done_in, start, end))
        ends = [b["end"] for b in batches]
        if len(ends) > 1:
            counters["stall_pct"] = 100.0 * estimators.stall_share(
                done_in, estimators.median(
                    [b - a for a, b in zip(ends, ends[1:])]), start, end)
    else:
        latency = [(r["done"] - r["due"]) * 1e3 for r in good]
        e2e["serve_p50_ms"] = estimators.quantile(latency, 0.5)
        e2e["serve_p95_ms"] = estimators.quantile(latency, 0.95)
        late = [(r["sent"] - r["due"]) * 1e3 for r in due]
        half = start + 0.5 * (end - start)
        first = [(r["done"] - r["due"]) * 1e3 for r in good if r["due"] < half]
        second = [(r["done"] - r["due"]) * 1e3 for r in good if r["due"] >= half]
        info.update(
            latency_readings=len(latency),
            readings_beyond_p95=estimators.samples_beyond(len(latency), 0.95),
            generator_late_p99_ms=estimators.quantile(late, 0.99),
            generator_late_max_ms=max(late),
            offered_per_s=len(due) / (end - start),
            p50_first_half_ms=estimators.median(first) if first else None,
            p50_second_half_ms=estimators.median(second) if second else None,
            unanswered_at_window_end=sum(
                1 for r in due if r["done"] is None or r["done"] > end))
        late_ok = info["generator_late_p99_ms"] <= mix["max_late_p99_ms"]
        series["latency_ms"] = latency
    with_server = [r for r in good if "server" in r]
    series["hop_ms"] = [
        (r["done"] - r["sent"]) * 1e3 - r["server"]["queue_ms"]
        - r["server"]["forward_ms"] for r in with_server]
    series["queue_ms"] = [r["server"]["queue_ms"] for r in with_server]
    series["forward_ms"] = [b["forward_ms"] for b in batches]
    series["batch_requests"] = sizes
    counters.update(
        tokens=sum(b["tokens"] for b in batches),
        padded_tokens=sum(b["padded_tokens"] for b in batches),
        window_compiles=w["replica"]["compiles"])
    failed = len(due) - len(good)
    errors = sorted({r["error"] for r in due if "error" in r})[:3]
    return {"end_to_end": e2e, "info": info, "counters": counters,
            "series": series, "attempted": len(due), "failed": failed,
            "errors": errors,
            "correct": bool(facts["check"]["ok"] and failed == 0 and late_ok
                            and w["replica"]["compiles"] == 0)}
