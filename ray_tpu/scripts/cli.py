"""CLI: cluster lifecycle, state inspection, job control.

Reference parity: python/ray/scripts/scripts.py (command registry
:2545-2604 — start/stop/status/timeline/job/list). Invoke as
`python -m ray_tpu <command>`.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time


def _address(args) -> str:
    addr = getattr(args, "address", None) or os.environ.get(
        "RAY_TPU_ADDRESS", "")
    if not addr:
        sys.exit("error: --address (or RAY_TPU_ADDRESS) is required")
    return addr


def _connect(args):
    import ray_tpu
    ray_tpu.init(address=_address(args))
    return ray_tpu


# ---------------------------------------------------------------- start/stop

def cmd_start(args):
    """Run a head (GCS + raylet) or worker (raylet) node in the foreground."""
    import asyncio

    from ray_tpu._private.config import Config, set_config
    from ray_tpu._private.node import HeadNode, detect_node_resources
    from ray_tpu._private.raylet import Raylet
    from ray_tpu._private.node import new_session_dir

    config = Config.load(None)
    set_config(config)
    res = detect_node_resources(args.num_cpus, args.num_tpus, None, config)
    if args.memory is not None:
        res["memory"] = float(args.memory)

    async def _run_head():
        head = HeadNode(config, resources=res,
                        object_store_memory=args.object_store_memory)
        gcs_address = await head.start(port=args.port)
        print(f"ray_tpu head started; GCS at {gcs_address}", flush=True)
        print(f"connect with: ray_tpu.init(address='{gcs_address}') or "
              f"RAY_TPU_ADDRESS={gcs_address}", flush=True)
        if args.client_server_port:
            from ray_tpu.util.client import ClientServer
            cs = ClientServer(gcs_address)
            addr = await cs.start(port=args.client_server_port)
            head.client_server = cs
            print(f"client server at ray_tpu://{addr}", flush=True)
        return head

    async def _run_worker():
        session_dir = new_session_dir(config)
        raylet = Raylet(config, args.address, session_dir, resources=res,
                        object_store_memory=args.object_store_memory)
        await raylet.start()
        print(f"ray_tpu worker node joined {args.address}", flush=True)
        return raylet

    async def _main():
        node = await (_run_head() if args.head else _run_worker())
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        await stop.wait()
        await node.stop()

    if not args.head and not args.address:
        sys.exit("error: worker nodes need --address=<gcs host:port>")
    asyncio.run(_main())


def cmd_status(args):
    ray_tpu = _connect(args)
    from ray_tpu.util.state import cluster_status
    st = cluster_status()
    print(f"nodes: {st['nodes_alive']} alive, {st['nodes_dead']} dead")
    print("resources:")
    avail = st["available_resources"]
    for k, v in sorted(st["cluster_resources"].items()):
        print(f"  {k}: {avail.get(k, 0):g}/{v:g} available")
    if st["actors"]:
        print("actors:", dict(st["actors"]))
    if st["placement_groups"]:
        print("placement groups:", dict(st["placement_groups"]))
    try:
        from ray_tpu._private import worker_api
        core = worker_api.get_core()
        addr = worker_api._call_on_core_loop(
            core, core.gcs.request("get_metrics_address", {}), 10)
        if addr:
            print(f"metrics: http://{addr}/metrics "
                  f"(status: http://{addr}/api/status)")
    except Exception:
        pass
    ray_tpu.shutdown()


# ---------------------------------------------------------------- state

def cmd_list(args):
    ray_tpu = _connect(args)
    from ray_tpu.util import state
    fns = {
        "nodes": state.list_nodes, "actors": state.list_actors,
        "tasks": state.list_tasks, "jobs": state.list_jobs,
        "objects": state.list_objects,
        "placement-groups": state.list_placement_groups,
    }
    rows = fns[args.entity]()
    print(json.dumps(rows, indent=2, default=str))
    ray_tpu.shutdown()


def cmd_summary(args):
    ray_tpu = _connect(args)
    from ray_tpu.util.state import summarize_task_latency, summarize_tasks
    print(json.dumps(summarize_tasks(), indent=2))
    rows = summarize_task_latency()
    if rows:
        # Flight-recorder latency columns: p50/p95 per lifecycle phase.
        print(f"\n{'name':<24}{'phase':<16}{'count':>7}"
              f"{'p50 ms':>10}{'p95 ms':>10}")
        for r in rows:
            print(f"{r['name']:<24.24}{r['phase']:<16}{r['count']:>7}"
                  f"{r['p50_ms']:>10.3f}{r['p95_ms']:>10.3f}")
    ray_tpu.shutdown()


def cmd_timeline(args):
    ray_tpu = _connect(args)
    trace = ray_tpu.timeline(device_trace=args.device_trace)
    rid = getattr(args, "request", None)
    if rid:
        # One serve request's trace only: every row stamped with the
        # request id (proxy/replica hops, replay markers, handler spans).
        trace = [t for t in trace if t.get("request_id") == rid]
    out = args.output or "timeline.json"
    with open(out, "w") as f:
        json.dump(trace, f)
    print(f"wrote {len(trace)} events to {out}")
    ray_tpu.shutdown()


def cmd_top(args):
    """Live cluster dashboard over the GCS time-series store."""
    from ray_tpu.scripts import top
    top.run(args)


def cmd_traces(args):
    """Search the GCS serve-request trace buffer (slow / failed requests)."""
    ray_tpu = _connect(args)
    from ray_tpu._private import worker_api
    core = worker_api.get_core()
    rows = worker_api._call_on_core_loop(
        core,
        core.gcs.request("search_traces", {
            "deployment": args.deployment,
            "min_ms": args.min_ms,
            "errors_only": args.errors_only,
            "limit": args.limit,
        }), 30)
    if not rows:
        print("no matching requests")
    else:
        print(f"{'request_id':<34}{'deployment':<18}{'ms':>9}"
              f"{'hops':>6}{'replays':>8}  error")
        for r in rows:
            print(f"{r['request_id']:<34.33}{r['deployment']:<18.17}"
                  f"{r['total_ms']:>9.1f}{r['hops']:>6}{r['replays']:>8}"
                  f"  {r.get('error') or ''}")
        print(f"\n{len(rows)} request(s); inspect one with: "
              f"python -m ray_tpu timeline --request <request_id>")
    ray_tpu.shutdown()


def cmd_stack(args):
    """`ray stack` equivalent: thread dumps / CPU samples / heap snapshots
    from a live worker over its profiling RPCs (reference:
    dashboard/modules/reporter/profile_manager.py)."""
    ray_tpu = _connect(args)
    from ray_tpu._private import worker_api
    core = worker_api.get_core()

    method = {"stack": "stack_dump", "cpu": "profile_cpu",
              "memory": "profile_memory"}[args.kind]
    payload = {"duration_s": args.duration} if args.kind == "cpu" else {}

    async def probe():
        return await core.clients.request(args.worker_address, method,
                                          payload, timeout=60)

    out = worker_api._call_on_core_loop(core, probe(), 90)
    if args.kind == "stack":
        for thread, stack in out.items():
            print(f"--- {thread} ---\n{stack}")
    else:
        print(json.dumps(out, indent=2, default=str))
    ray_tpu.shutdown()


def cmd_up(args):
    """`ray up` equivalent: config-driven cluster bring-up, attached
    (head + provider + autoscaler run in this process until Ctrl-C)."""
    import time as _time

    from ray_tpu.autoscaler import create_or_update_cluster

    launcher = create_or_update_cluster(args.config)
    print(f"cluster '{launcher.config['cluster_name']}' up; GCS at "
          f"{launcher.gcs_address}", flush=True)
    print(f"connect with: ray_tpu.init(address='{launcher.gcs_address}')",
          flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("tearing down...", flush=True)
        launcher.teardown()


def cmd_down(args):
    from ray_tpu.autoscaler import load_cluster_config, teardown_cluster
    cfg = load_cluster_config(args.config)
    if cfg["provider"].get("type", "fake") == "fake":
        print("fake-provider clusters live in the `up` process — stop "
              "them with Ctrl-C there; nothing to terminate from here",
              flush=True)
        return
    n = teardown_cluster(args.config)
    print(f"terminated {n} provider node(s)", flush=True)


def cmd_kv_store(args):
    """Standalone external GCS state store (the Redis-equivalent;
    reference: redis_store_client.h). Point heads at it with
    RAY_TPU_GCS_STORAGE_ADDRESS=host:port."""
    from ray_tpu._private.kv_store import run_server
    run_server(args.host, args.port, args.dir)


def cmd_serve(args):
    """Serve CLI (reference: python/ray/serve/scripts.py): deploy a
    config file, run an import path, or print app status — against the
    cluster at --address."""
    ray_tpu = _connect(args)
    from ray_tpu import serve
    if args.serve_cmd == "deploy":
        deployed = serve.deploy_config(args.config)
        print(f"deployed applications: {', '.join(deployed)}")
    elif args.serve_cmd == "run":
        serve.run_import_path(args.import_path, name=args.name,
                              route_prefix=args.route_prefix)
        print(f"app '{args.name}' running at route {args.route_prefix}; "
              f"Ctrl-C to exit", flush=True)
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            serve.delete(args.name)
    elif args.serve_cmd == "status":
        print(json.dumps(serve.status(), indent=2, default=str))
    ray_tpu.shutdown()


# ---------------------------------------------------------------- rllib

def cmd_rllib(args):
    """RLlib CLI (reference: rllib/train.py `rllib train` +
    rllib/evaluate.py `rllib evaluate`): run an algorithm on an env from
    the command line; evaluate a saved checkpoint greedily."""
    import cloudpickle

    import ray_tpu
    from ray_tpu import rllib as rl
    config_cls = getattr(rl, f"{args.algo}Config", None)
    if config_cls is None:
        sys.exit(f"error: unknown algorithm {args.algo!r}; see "
                 f"ray_tpu.rllib.__all__ for available *Config classes")
    config_json = args.config
    if args.rllib_cmd == "evaluate":
        # Usage errors before paying for init + actor spawns.
        if not args.checkpoint_path:
            sys.exit("error: evaluate needs --checkpoint-path")
        with open(args.checkpoint_path, "rb") as f:
            ckpt = cloudpickle.load(f)
        # Train-time config rides in the checkpoint so evaluate builds
        # the SAME network without the user repeating --config.
        if not config_json:
            config_json = ckpt.get("cli_config", "")
        saved_env = ckpt.get("cli_env")
        if saved_env and saved_env != args.env:
            sys.exit(f"error: checkpoint was trained on env "
                     f"{saved_env!r}; pass --env {saved_env}")
    cfg = config_cls().environment(args.env)
    if config_json:
        try:
            overrides = json.loads(config_json)
            if not isinstance(overrides, dict):
                raise ValueError("--config must be a JSON object")
            cfg.training(**overrides)
        except (json.JSONDecodeError, TypeError, ValueError) as e:
            sys.exit(f"error: bad --config: {e}")
    if args.rllib_cmd == "evaluate":
        if cfg.is_multi_agent:
            sys.exit("error: evaluate supports single-policy "
                     "checkpoints only")
        from ray_tpu.rllib.env import make_env
        if make_env(args.env, cfg.env_config).continuous:
            sys.exit("error: evaluate supports discrete-action "
                     "policy/Q algorithms only")
        cfg.env_runners(num_env_runners=1)  # one greedy evaluator
    ray_tpu.init(num_cpus=args.num_cpus, num_tpus=0)
    try:
        algo = cfg.build()
        if args.rllib_cmd == "train":
            best = float("-inf")
            for i in range(args.stop_iters):
                r = algo.step()
                rew = r.get("episode_reward_mean", float("nan"))
                if rew == rew:
                    best = max(best, rew)
                print(f"iter {i + 1}: reward_mean="
                      f"{rew if rew == rew else 'n/a'} "
                      f"episodes={r.get('episodes_total', 0)}", flush=True)
                if args.stop_reward is not None and rew == rew \
                        and rew >= args.stop_reward:
                    print(f"stop-reward {args.stop_reward} reached")
                    break
            if best > float("-inf"):
                print(f"best reward_mean: {best:.2f}")
            if args.checkpoint_path:
                state = algo.save_checkpoint()
                state["cli_config"] = args.config
                state["cli_env"] = args.env
                with open(args.checkpoint_path, "wb") as f:
                    cloudpickle.dump(state, f)
                print(f"checkpoint written to {args.checkpoint_path}")
        else:  # evaluate
            if not hasattr(algo, "learner"):
                sys.exit(f"error: {args.algo} has no single-learner "
                         f"checkpoint to evaluate")
            ckpt.pop("cli_config", None)
            ckpt.pop("cli_env", None)
            algo.load_checkpoint(ckpt)
            weights = algo.learner.get_weights()
            ret = ray_tpu.get(
                algo.env_runners[0].evaluate_return.remote(
                    weights, episodes=args.episodes), timeout=600)
            print(f"mean_return={ret:.2f} over {args.episodes} episodes")
        algo.cleanup()
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------- jobs

def cmd_job(args):
    from ray_tpu.job_submission import JobSubmissionClient
    client = JobSubmissionClient(_address(args))
    if args.job_cmd == "submit":
        sid = client.submit_job(entrypoint=" ".join(args.entrypoint))
        print(sid)
        if args.wait:
            status = client.wait_until_finish(sid, timeout=args.timeout)
            print(status)
            print(client.get_job_logs(sid), end="")
            sys.exit(0 if status == "SUCCEEDED" else 1)
    elif args.job_cmd == "status":
        print(client.get_job_status(args.submission_id))
    elif args.job_cmd == "logs":
        print(client.get_job_logs(args.submission_id), end="")
    elif args.job_cmd == "stop":
        print(client.stop_job(args.submission_id))
    elif args.job_cmd == "list":
        print(json.dumps(client.list_jobs(), indent=2))


# ---------------------------------------------------------------- parser

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="ray_tpu", description="ray_tpu cluster CLI")
    sub = p.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("start", help="start a head or worker node")
    s.add_argument("--head", action="store_true")
    s.add_argument("--address", default="", help="GCS address (worker mode)")
    s.add_argument("--port", type=int, default=6379)
    s.add_argument("--num-cpus", type=float, default=None, dest="num_cpus")
    s.add_argument("--num-tpus", type=float, default=None, dest="num_tpus")
    s.add_argument("--memory", type=int, default=None,
                   help="node memory resource in bytes")
    s.add_argument("--object-store-memory", type=int, default=None,
                   dest="object_store_memory",
                   help="plasma arena size in bytes")
    s.add_argument("--client-server-port", type=int, default=0,
                   dest="client_server_port",
                   help="serve remote ray_tpu:// clients on this port")
    s.set_defaults(fn=cmd_start)

    s = sub.add_parser("status", help="cluster status")
    s.add_argument("--address", default=None)
    s.set_defaults(fn=cmd_status)

    s = sub.add_parser("list", help="list cluster entities")
    s.add_argument("entity", choices=["nodes", "actors", "tasks", "jobs",
                                      "objects", "placement-groups"])
    s.add_argument("--address", default=None)
    s.set_defaults(fn=cmd_list)

    s = sub.add_parser("summary", help="task state summary")
    s.add_argument("--address", default=None)
    s.set_defaults(fn=cmd_summary)

    s = sub.add_parser("timeline", help="dump chrome-trace timeline")
    s.add_argument("--address", default=None)
    s.add_argument("-o", "--output", default=None)
    s.add_argument("--request", default=None,
                   help="filter to one serve request id (X-Request-Id)")
    s.add_argument("--device-trace", default=None, metavar="DIR",
                   help="a util/profiling.device_trace directory with the "
                        "compiled step's *.hlo.txt in it: adds a lane a "
                        "chip, the device's ops by region")
    s.set_defaults(fn=cmd_timeline)

    s = sub.add_parser("top", help="live cluster dashboard "
                                   "(tsdb-backed, ANSI redraw)")
    s.add_argument("--address", default=None)
    s.add_argument("--once", action="store_true",
                   help="print a single frame and exit (no ANSI)")
    s.add_argument("--interval", type=float, default=2.0)
    s.add_argument("--window", type=float, default=300.0,
                   help="query window in seconds")
    s.set_defaults(fn=cmd_top)

    s = sub.add_parser("traces", help="search serve request traces")
    s.add_argument("--address", default=None)
    s.add_argument("--deployment", default=None)
    s.add_argument("--min-ms", type=float, default=0.0,
                   help="only requests slower than this end-to-end")
    s.add_argument("--errors-only", action="store_true")
    s.add_argument("--limit", type=int, default=50)
    s.set_defaults(fn=cmd_traces)

    s = sub.add_parser("profile", help="profile a live worker "
                                       "(stack/cpu/memory)")
    s.add_argument("kind", choices=["stack", "cpu", "memory"])
    s.add_argument("worker_address", help="worker RPC address host:port "
                                          "(see `list workers`)")
    s.add_argument("--address", default=None)
    s.add_argument("--duration", type=float, default=2.0)
    s.set_defaults(fn=cmd_stack)

    s = sub.add_parser("up", help="bring up a cluster from a config YAML")
    s.add_argument("config")
    s.set_defaults(fn=cmd_up)

    s = sub.add_parser("down", help="terminate a cluster's provider nodes")
    s.add_argument("config")
    s.set_defaults(fn=cmd_down)

    s = sub.add_parser("kv-store", help="run the standalone external "
                                        "GCS state store")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=0)
    s.add_argument("--dir", default="/tmp/ray_tpu_kv_store")
    s.set_defaults(fn=cmd_kv_store)

    s = sub.add_parser("serve", help="serve deploy/run/status")
    ssub = s.add_subparsers(dest="serve_cmd", required=True)
    sd = ssub.add_parser("deploy")
    sd.add_argument("config")
    sd.add_argument("--address", default=None)
    sd.set_defaults(fn=cmd_serve)
    sr = ssub.add_parser("run")
    sr.add_argument("import_path")
    sr.add_argument("--name", default="default")
    sr.add_argument("--route-prefix", default="/", dest="route_prefix")
    sr.add_argument("--address", default=None)
    sr.set_defaults(fn=cmd_serve)
    st = ssub.add_parser("status")
    st.add_argument("--address", default=None)
    st.set_defaults(fn=cmd_serve)

    s = sub.add_parser("rllib", help="rllib train/evaluate")
    rsub = s.add_subparsers(dest="rllib_cmd", required=True)
    for name in ("train", "evaluate"):
        r = rsub.add_parser(name)
        r.add_argument("--algo", default="PPO",
                       help="algorithm name (PPO, A2C, PG, DQN, C51, "
                            "QRDQN, ...; evaluate needs a "
                            "discrete-action single-learner algo)")
        r.add_argument("--env", default="CartPole-v1")
        r.add_argument("--config", default="",
                       help="JSON dict of .training(...) overrides")
        r.add_argument("--num-cpus", type=int, default=4,
                       dest="num_cpus")
        r.add_argument("--checkpoint-path", default="",
                       dest="checkpoint_path")
        if name == "train":
            r.add_argument("--stop-iters", type=int, default=10,
                           dest="stop_iters")
            r.add_argument("--stop-reward", type=float, default=None,
                           dest="stop_reward")
        else:
            r.add_argument("--episodes", type=int, default=5)
        r.set_defaults(fn=cmd_rllib)

    s = sub.add_parser("job", help="job submission")
    jsub = s.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--address", default=None)
    j.add_argument("--wait", action="store_true")
    j.add_argument("--timeout", type=float, default=300)
    j.add_argument("entrypoint", nargs=argparse.REMAINDER)
    j = jsub.add_parser("status")
    j.add_argument("submission_id")
    j.add_argument("--address", default=None)
    j = jsub.add_parser("logs")
    j.add_argument("submission_id")
    j.add_argument("--address", default=None)
    j = jsub.add_parser("stop")
    j.add_argument("submission_id")
    j.add_argument("--address", default=None)
    j = jsub.add_parser("list")
    j.add_argument("--address", default=None)
    s.set_defaults(fn=cmd_job)

    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
