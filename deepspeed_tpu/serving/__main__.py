"""``python -m deepspeed_tpu.serving``: run the serving gateway.

Builds an :class:`InferenceEngine` (continuous batching on), binds the HTTP
gateway, and serves until SIGTERM/SIGINT — which trigger a graceful drain:
readiness flips to 503, admitted requests finish, telemetry flushes, and
the process exits 0. Prints one ``GATEWAY_READY`` JSON line (with the bound
port — ``--port 0`` binds an ephemeral one) once accepting traffic.

Multi-host modes (``serving/router.py``):

- ``--worker --router-url http://HOST:PORT``: same gateway, but the process
  joins a cross-process fleet — it registers with the router, heartbeats
  capacity signals, and serves its slice of the networked prefix/handoff
  store. ``--worker-role prefill`` additionally hands finished prefills off
  to decode workers through that store.
- ``--router``: no model at all — run the router tier (placement + proxy +
  store directory). Prints one ``ROUTER_READY`` JSON line; optionally
  spawns a local worker fleet (``--spawn-workers N``) for smoke tests. A
  worker is a process and an accelerator belongs to one process, so N local
  workers need N accelerators each visible to one of them; a worker that
  cannot claim its own exits, and the router then exits non-zero with it.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time


def build_parser():
    p = argparse.ArgumentParser(prog="python -m deepspeed_tpu.serving",
                                description=__doc__.splitlines()[0])
    p.add_argument("--model", default="gpt2-large",
                   help="zoo model preset name (see deepspeed_tpu.models)")
    p.add_argument("--config", default=None,
                   help="path to a DeepSpeedInferenceConfig JSON (flags below "
                        "override its gateway/serving sections)")
    p.add_argument("--checkpoint", default=None, help="weights to load")
    p.add_argument("--dtype", default=None, help="serving dtype (bf16/int8/...)")
    p.add_argument("--host", default=None)
    p.add_argument("--port", type=int, default=None,
                   help="0 binds an ephemeral port (printed in GATEWAY_READY)")
    p.add_argument("--num-slots", type=int, default=None,
                   help="decode batch slots (continuous_batching.num_slots)")
    p.add_argument("--replicas", type=int, default=None,
                   help="scheduler replicas behind the gateway "
                        "(continuous_batching.replicas): independent slot "
                        "pools, one weight tree, one compiled program set")
    p.add_argument("--disagg-roles", default=None,
                   help="comma-separated per-replica phase roles "
                        "(prefill/decode/mixed), e.g. 'prefill,decode' — "
                        "enables continuous_batching.disaggregation: new "
                        "prompts place on prefill-capable replicas and "
                        "finished prefills migrate their KV to decode "
                        "replicas (runtime override: POST "
                        "/v1/replicas/<i>/role)")
    p.add_argument("--autoscale", action="store_true",
                   help="enable the elastic fleet controller "
                        "(continuous_batching.autoscaler.enabled): SLO-driven "
                        "replica scaling, phase re-balancing, and brownout "
                        "shedding, ticked from the serving pump (runtime "
                        "toggle + dry-run: POST /v1/autoscaler)")
    p.add_argument("--max-queue-depth", type=int, default=None)
    p.add_argument("--default-max-tokens", type=int, default=None)
    p.add_argument("--request-timeout-s", type=float, default=None)
    p.add_argument("--drain-timeout-s", type=float, default=None)
    p.add_argument("--kernel-inject", action="store_true",
                   help="enable the Pallas kernel-injected decode path")
    p.add_argument("--hierarchical-kv", action="store_true",
                   help="enable the hierarchical KV tier "
                        "(continuous_batching.hierarchical_kv.enabled) — the "
                        "networked prefix/handoff store rides on it, so "
                        "prefill-role workers require it")
    mh = p.add_argument_group("multi-host serving (serving/router.py)")
    mh.add_argument("--worker", action="store_true",
                    help="join a cross-process worker fleet: register with "
                         "--router-url, heartbeat capacity signals, serve "
                         "this process's slice of the networked "
                         "prefix/handoff store")
    mh.add_argument("--router-url", default=None,
                    help="router base URL the worker registers with")
    mh.add_argument("--worker-id", default=None,
                    help="fleet-unique worker id (default w<pid>)")
    mh.add_argument("--worker-role", default=None,
                    choices=("prefill", "decode", "mixed"),
                    help="process-level phase role (default mixed); "
                         "'prefill' hands finished prefills to decode "
                         "workers over the networked store")
    mh.add_argument("--heartbeat-s", type=float, default=None,
                    help="heartbeat cadence (multihost.heartbeat_interval_s)")
    mh.add_argument("--lease-s", type=float, default=None,
                    help="handoff claim deadline (multihost.lease_s)")
    mh.add_argument("--advertise-host", default=None,
                    help="host other processes dial to reach this worker")
    mh.add_argument("--migrate-min-tokens", type=int, default=None,
                    help="colocate threshold for cross-process handoff")
    mh.add_argument("--router", action="store_true",
                    help="run the ROUTER tier instead of a gateway (no "
                         "model): placement + proxy + store directory")
    mh.add_argument("--heartbeat-timeout-s", type=float, default=None,
                    help="router: a worker silent this long stops getting "
                         "placements")
    mh.add_argument("--spawn-workers", type=int, default=0,
                    help="router: also spawn N local worker processes "
                         "(inheriting --model/--dtype/... flags); smoke "
                         "tests and single-host fleets")
    mh.add_argument("--spawn-roles", default=None,
                    help="router: comma-separated roles for spawned workers "
                         "(e.g. 'prefill,decode'); default all mixed")
    return p


def run_router(args):
    """``--router``: the placement/proxy/directory tier. No engine, no JAX —
    the router is pure stdlib networking and can front any worker fleet."""
    from deepspeed_tpu.serving.router import Router

    router = Router(host=args.host or "127.0.0.1",
                    port=args.port if args.port is not None else 0,
                    heartbeat_timeout_s=args.heartbeat_timeout_s or 10.0)
    procs = []
    failed = []

    def on_ready():
        print(json.dumps({"event": "ROUTER_READY", "host": router.host,
                          "port": router.port}), flush=True)
        roles = ([r.strip() for r in args.spawn_roles.split(",") if r.strip()]
                 if args.spawn_roles else [])
        for i in range(args.spawn_workers):
            cmd = [sys.executable, "-m", "deepspeed_tpu.serving",
                   "--worker", "--router-url",
                   f"http://{router.host}:{router.port}",
                   "--worker-id", f"w{i}", "--model", args.model,
                   "--host", router.host, "--port", "0"]
            role = roles[i] if i < len(roles) else "mixed"
            cmd += ["--worker-role", role]
            if role == "prefill" or args.hierarchical_kv:
                cmd.append("--hierarchical-kv")
            for flag, name in (("dtype", "--dtype"),
                               ("checkpoint", "--checkpoint"),
                               ("config", "--config"),
                               ("num_slots", "--num-slots"),
                               ("replicas", "--replicas")):
                val = getattr(args, flag)
                if val is not None:
                    cmd += [name, str(val)]
            procs.append(subprocess.Popen(cmd))
        if procs:
            threading.Thread(target=watch_workers, daemon=True).start()

    def watch_workers():
        # an accelerator belongs to ONE process: a second local worker that
        # cannot claim its own dies at start-up. Take the fleet down with it
        # rather than route around a worker that never came up.
        while not failed:
            for i, proc in enumerate(procs):
                rc = proc.poll()
                if rc not in (None, 0, -signal.SIGTERM):
                    failed.append(rc)
                    print(f"deepspeed_tpu.serving: spawned worker w{i} exited "
                          f"with code {rc}. If it could not initialise its "
                          f"accelerator, another local process (another worker) "
                          f"holds the chip: one process per chip.",
                          file=sys.stderr, flush=True)
                    router.close()
                    return
            time.sleep(0.5)

    def shutdown(*_):
        for proc in procs:
            proc.terminate()
        router.close()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, shutdown)
    try:
        router.run(on_ready)
    finally:
        for proc in procs:
            proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    return 1 if failed else 0


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.router:
        return run_router(args)
    if args.worker and not args.router_url:
        build_parser().error("--worker requires --router-url")
    cfg = {}
    if args.config:
        with open(args.config) as f:
            cfg = json.load(f)
    cfg.setdefault("continuous_batching", {})["enabled"] = True
    if args.num_slots is not None:
        cfg["continuous_batching"]["num_slots"] = args.num_slots
    if args.replicas is not None:
        cfg["continuous_batching"]["replicas"] = args.replicas
    if args.autoscale:
        # merge: keep any tuned autoscaler thresholds from the config file
        cfg["continuous_batching"].setdefault("autoscaler", {})["enabled"] = True
    if args.disagg_roles is not None:
        # merge, don't replace: a config file's migrate_min_tokens (etc.)
        # must survive the CLI setting the roles
        dg = cfg["continuous_batching"].setdefault("disaggregation", {})
        dg["enabled"] = True
        dg["roles"] = [r.strip() for r in args.disagg_roles.split(",") if r.strip()]
    if args.dtype is not None:
        cfg["dtype"] = args.dtype
    if args.checkpoint is not None:
        cfg["checkpoint"] = args.checkpoint
    if args.kernel_inject:
        cfg["kernel_inject"] = True
    if args.hierarchical_kv:
        cfg["continuous_batching"].setdefault("hierarchical_kv",
                                              {})["enabled"] = True
    mh_cfg = cfg["continuous_batching"].setdefault("multihost", {})
    for flag, key in (("router_url", "router_url"),
                      ("worker_id", "worker_id"),
                      ("worker_role", "worker_role"),
                      ("heartbeat_s", "heartbeat_interval_s"),
                      ("lease_s", "lease_s"),
                      ("advertise_host", "advertise_host"),
                      ("migrate_min_tokens", "migrate_min_tokens")):
        val = getattr(args, flag)
        if val is not None:
            mh_cfg[key] = val
    gw_cfg = cfg.setdefault("gateway", {})
    for flag, key in (("host", "host"), ("port", "port"),
                      ("max_queue_depth", "max_queue_depth"),
                      ("default_max_tokens", "default_max_tokens"),
                      ("request_timeout_s", "request_timeout_s"),
                      ("drain_timeout_s", "drain_timeout_s")):
        val = getattr(args, flag)
        if val is not None:
            gw_cfg[key] = val

    import deepspeed_tpu
    from deepspeed_tpu.serving import Gateway
    from deepspeed_tpu.utils import compile_cache

    compile_cache.configure()
    engine = deepspeed_tpu.init_inference(args.model, config=cfg)
    gateway = Gateway(engine)
    # At real depth a step program compiles for longer than the default
    # request deadline, so a cold server would expire its first requests
    # mid-compile. Compile what plain traffic dispatches before reporting
    # ready (one program set serves every replica; seconds once the compile
    # cache is warm).
    gateway.scheduler.warm_programs(ladder=False)
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: gateway.begin_drain())
    if hasattr(signal, "SIGUSR1"):
        # operator-forced flight-recorder dump (kill -USR1 <pid>): the
        # handler only flags the request — the pump thread performs the
        # dump (taking sink locks in signal context can self-deadlock)
        signal.signal(signal.SIGUSR1,
                      lambda *_: gateway.request_flight_dump("sigusr1"))
    if args.worker:
        from deepspeed_tpu.serving.router import WorkerAgent

        gateway.start_background()
        agent = WorkerAgent(
            gateway, args.router_url,
            mh_cfg.get("worker_id") or f"w{os.getpid()}",
            role=mh_cfg.get("worker_role", "mixed"),
            heartbeat_s=mh_cfg.get("heartbeat_interval_s", 2.0),
            lease_s=mh_cfg.get("lease_s", 30.0),
            advertise_host=mh_cfg.get("advertise_host"),
            migrate_min_tokens=mh_cfg.get("migrate_min_tokens", 0))
        agent.attach()
        agent.start()
        print(json.dumps({"event": "GATEWAY_READY", "host": gateway.host,
                          "port": gateway.port, "worker_id": agent.wid,
                          "role": agent.role}), flush=True)
        while not gateway.wait_drained(0.2):
            pass
        agent.stop()
        return 0
    return gateway.run()


if __name__ == "__main__":
    sys.exit(main())
