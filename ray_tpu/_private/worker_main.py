"""Worker process entrypoint (reference: python/ray/_private/workers/default_worker.py).

Spawned by the raylet; registers back over RPC, then serves pushed tasks until
told to shut down.
"""

from __future__ import annotations

import asyncio
import logging
import os
import sys


def main():
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s worker[%(process)d] %(name)s: %(message)s")
    # Debugging hook (reference: `ray stack` via py-spy): SIGUSR1 dumps all
    # thread stacks to the worker's log file.
    try:
        import faulthandler
        import signal as _signal
        faulthandler.register(_signal.SIGUSR1, all_threads=True)
    except Exception:
        pass
    # Honor this worker's JAX_PLATFORMS. JAX reads the variable when it is
    # imported, and a forked worker inherits the zygote's already-imported
    # jax (worker_forkserver._warm_imports), whose value is the zygote's,
    # not the one this worker's env was reset to. Mirror it into the
    # config; no backend is initialized here.
    plat = os.environ.get("JAX_PLATFORMS")
    if plat:
        import jax
        jax.config.update("jax_platforms", plat)
    raylet_address = os.environ["RAY_TPU_RAYLET_ADDRESS"]
    gcs_address = os.environ["RAY_TPU_GCS_ADDRESS"]
    session_dir = os.environ.get("RAY_TPU_SESSION_DIR", "")

    from ray_tpu._private import rpc
    from ray_tpu._private.config import Config, set_config
    from ray_tpu._private.core_worker import CoreWorker
    from ray_tpu._private.ids import NodeID, WorkerID

    worker_id = WorkerID.from_hex(os.environ["RAY_TPU_WORKER_ID"])
    node_id = NodeID.from_hex(os.environ["RAY_TPU_NODE_ID"])

    async def run():
        config = Config.load()
        core = CoreWorker("worker", gcs_address, raylet_address, config,
                          worker_id=worker_id, node_id=node_id,
                          session_dir=session_dir)
        await core.start_async()
        # Make the public API (ray_tpu.get/put/remote inside tasks) reentrant.
        from ray_tpu._private import worker_api
        worker_api._worker_core.core = core
        # Register with the raylet so it can hand out leases to us. The
        # push handler is live from the first frame: the raylet delivers
        # warm-path actor constructions as a PUSH over this connection
        # (no per-create dial back to our server).
        conn_cell = {}

        async def _instantiate_and_report(payload):
            try:
                result = await core._rpc_instantiate_actor(None, payload)
            except BaseException as e:  # noqa: BLE001
                # Nothing awaits this task: an escaped error would leave
                # the raylet's result future waiting out the full create
                # timeout. Ship it as an infra error instead — the
                # raylet re-raises it into the create path (same
                # semantics the old request/reply dispatch had).
                import traceback
                result = {"_infra_error":
                          f"{type(e).__name__}: {e}\n"
                          f"{traceback.format_exc()}"}
            try:
                # notify, not request: the raylet's result future is the
                # ack (its create path times out if this frame is lost
                # with the connection — same failure semantics).
                await conn_cell["conn"].notify("instantiate_result", {
                    "worker_id": worker_id, "result": result})
            except Exception:
                logging.getLogger(__name__).exception(
                    "instantiate_result report failed")

        def _raylet_push(method, payload):
            if method == "shutdown":
                core.loop.call_soon(core.loop.stop)
            elif method == "instantiate_actor":
                return _instantiate_and_report(payload)

        raylet_conn = await rpc.connect(raylet_address, _raylet_push)
        conn_cell["conn"] = raylet_conn
        reply = await raylet_conn.request("register_worker", {
            "worker_id": worker_id, "pid": os.getpid(),
            "address": core.address,
        })
        set_config(Config.load(reply["config"]))

        assignment = reply.get("assignment")
        if assignment is not None:
            # First assignment rode the registration reply (an actor
            # create was waiting for this worker): construct immediately
            # and report the outcome over this same connection — no
            # idle→re-offer→instantiate dial round trip.
            asyncio.ensure_future(_instantiate_and_report(assignment))

        # The raylet pushes "shutdown" notifications over this connection.
        async def watch_raylet():
            while True:
                await asyncio.sleep(0.5)
                if raylet_conn.closed:
                    core.loop.stop()
                    return
        asyncio.ensure_future(watch_raylet())
        core.server.register("shutdown", _make_shutdown(core))
        return core, raylet_conn

    loop = asyncio.new_event_loop()
    asyncio.set_event_loop(loop)
    core_and_conn = loop.run_until_complete(run())
    core, raylet_conn = core_and_conn

    # raylet "shutdown" / "instantiate_actor" pushes are handled by the
    # push handler installed at connect time (see _raylet_push above);
    # notify-style shutdown also arrives as a request on our server.
    del raylet_conn  # kept alive by the run() closure

    profile_dir = os.environ.get("RAY_TPU_PROFILE_WORKER")
    prof = None
    if profile_dir:
        import cProfile
        import signal as _sig
        prof = cProfile.Profile()

        def _dump_profile(*_a):
            prof.disable()
            prof.dump_stats(
                os.path.join(profile_dir, f"worker-{os.getpid()}.prof"))
            os._exit(0)

        _sig.signal(_sig.SIGTERM, _dump_profile)
        prof.enable()
    try:
        loop.run_forever()
    finally:
        if prof is not None:
            prof.disable()
            prof.dump_stats(
                os.path.join(profile_dir, f"worker-{os.getpid()}.prof"))
        try:
            loop.run_until_complete(core.shutdown_async())
        except Exception:
            pass
        sys.exit(0)


def _make_shutdown(core):
    async def _shutdown(conn, payload):
        core.loop.call_soon(core.loop.stop)
        return True
    return _shutdown


if __name__ == "__main__":
    main()
