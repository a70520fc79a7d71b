"""Multi-host launcher.

TPU-native analogue of the reference launcher (``launcher/runner.py:377``
``main``, hostfile parsing :189, include/exclude filters :244, and the
per-node ``launcher/launch.py``). Key design translation: DeepSpeed spawns
ONE PROCESS PER GPU per node; JAX on TPU runs ONE PROCESS PER HOST and the
runtime sees every local chip, so the launcher's job collapses to: resolve
the host list, pick a coordinator, and start one bootstrap per host over ssh
with ``JAX_PROCESS_ID``/``JAX_NUM_PROCESSES``/``COORDINATOR_ADDRESS`` set
(consumed by ``deepspeed_tpu.comm.init_distributed`` →
``jax.distributed.initialize``). GPU-style ``slots=N`` hostfile syntax is
accepted for config compatibility; slots do not multiply processes.

Single-host invocations exec the script directly (no ssh), matching the
reference's local fast path.
"""

import argparse
import os
import shlex
import subprocess
import sys

from ..utils import compile_cache
from ..utils.logging import logger

DEFAULT_COORD_PORT = 8476


def fetch_hostfile(hostfile_path):
    """Parse a DeepSpeed-style hostfile: one ``hostname [slots=N]`` per line,
    ``#`` comments. Returns an ordered {hostname: slots} dict (reference
    ``runner.py:189``)."""
    if not os.path.isfile(hostfile_path):
        raise FileNotFoundError(f"hostfile {hostfile_path} not found")
    resources = {}
    with open(hostfile_path) as f:
        for lineno, raw in enumerate(f, 1):
            line = raw.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.split()
            host = parts[0]
            slots = 1
            for tok in parts[1:]:
                key, _, val = tok.partition("=")
                if key == "slots":
                    try:
                        slots = int(val)
                    except ValueError:
                        raise ValueError(f"hostfile line {lineno}: bad slots value {val!r}")
                else:
                    raise ValueError(f"hostfile line {lineno}: unknown token {tok!r}")
            if host in resources:
                raise ValueError(f"hostfile line {lineno}: duplicate host {host}")
            resources[host] = slots
    if not resources:
        raise ValueError(f"hostfile {hostfile_path} is empty")
    return resources


def parse_inclusion_exclusion(resources, include_str="", exclude_str=""):
    """Apply ``--include``/``--exclude`` node filters (reference
    ``runner.py:244``). Syntax: ``node1@node2`` or ``node1:0,1`` — the
    ``:slot`` form is accepted and restricts slot counts for parity, though
    slots do not multiply TPU processes."""
    if include_str and exclude_str:
        raise ValueError("--include and --exclude are mutually exclusive")

    def parse_spec(spec):
        wanted = {}
        for node_spec in spec.split("@"):
            node_spec = node_spec.strip()
            if not node_spec:
                continue
            host, _, slot_str = node_spec.partition(":")
            if host not in resources:
                raise ValueError(f"filter references unknown host {host!r}")
            wanted[host] = ([int(s) for s in slot_str.split(",")] if slot_str else None)
        return wanted

    if include_str:
        keep = parse_spec(include_str)
        return {h: (len(s) if s is not None else resources[h]) for h, s in keep.items()}
    if exclude_str:
        drop = parse_spec(exclude_str)
        out = {}
        for host, slots in resources.items():
            if host not in drop:
                out[host] = slots
            elif drop[host] is not None:  # partial slot exclusion
                remaining = slots - len(drop[host])
                if remaining > 0:
                    out[host] = remaining
        if not out:
            raise ValueError("exclusion filter removed every host")
        return out
    return dict(resources)


def build_host_commands(hosts, coordinator, port, script, script_args, env_passthrough=()):
    """One (host, argv, env) per process. Host 0 runs the coordinator."""
    cmds = []
    n = len(hosts)
    for pid, host in enumerate(hosts):
        env = {
            "COORDINATOR_ADDRESS": f"{coordinator}:{port}",
            "JAX_NUM_PROCESSES": str(n),
            "JAX_PROCESS_ID": str(pid),
        }
        for key in env_passthrough:
            if key in os.environ:
                env[key] = os.environ[key]
        argv = [sys.executable, "-u", script] + list(script_args)
        cmds.append((host, argv, env))
    return cmds


def _ssh_wrap(host, argv, env, ssh_port=None, tty=False):
    """``tty=True`` (elastic mode): allocate a pty so terminating the LOCAL
    ssh client HUPs the remote process group — without it, killing the ssh
    client leaves remote workers alive holding the TPU across relaunches."""
    exports = " ".join(f"export {k}={shlex.quote(v)};" for k, v in env.items())
    remote = f"cd {shlex.quote(os.getcwd())}; {exports} {' '.join(shlex.quote(a) for a in argv)}"
    cmd = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if tty:
        cmd += ["-tt"]
    if ssh_port:
        cmd += ["-p", str(ssh_port)]
    return cmd + [host, remote]


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        prog="deepspeed-tpu",
        description="Launch a deepspeed_tpu training script on one or many TPU hosts")
    parser.add_argument("-H", "--hostfile", default="/job/hostfile",
                        help="hostfile of ssh-reachable TPU-VM hosts")
    parser.add_argument("-i", "--include", default="", help="node filter, e.g. host1@host2")
    parser.add_argument("-e", "--exclude", default="", help="node filter, e.g. host3")
    parser.add_argument("--num_nodes", type=int, default=-1, help="use first N hosts")
    parser.add_argument("--master_addr", default=None, help="coordinator address override")
    parser.add_argument("--master_port", type=int, default=DEFAULT_COORD_PORT)
    parser.add_argument("--ssh_port", type=int, default=None)
    parser.add_argument("--force_multi", action="store_true",
                        help="use ssh launch even for one host")
    parser.add_argument("--launcher", default="ssh",
                        choices=["ssh", "pdsh", "openmpi", "mpich", "mvapich", "slurm"],
                        help="multinode backend (reference multinode_runner.py variants); "
                             "'ssh' is the built-in loop")
    parser.add_argument("--launcher_args", default="",
                        help="extra args appended to the backend command (parity knob)")
    parser.add_argument("--slurm_comment", default="", help="srun --comment value")
    parser.add_argument("--elastic", action="store_true",
                        help="supervise workers and relaunch on failure/preemption "
                             "(workers auto-resume from the latest checkpoint)")
    parser.add_argument("--max_elastic_restarts", type=int, default=3)
    parser.add_argument("user_script", help="training script")
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(argv)


def _resolve_hosts(args):
    if os.path.isfile(args.hostfile):
        resources = fetch_hostfile(args.hostfile)
        resources = parse_inclusion_exclusion(resources, args.include, args.exclude)
        hosts = list(resources)
    else:
        hosts = ["localhost"]
    if args.num_nodes > 0:
        hosts = hosts[:args.num_nodes]
    return hosts


# XLA_FLAGS rides along for CPU-hosted fleets (forced host device counts —
# the multi-host serving smoke path spawns workers with
# --xla_force_host_platform_device_count and the workers must see it)
_ENV_PASSTHROUGH = ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS",
                    "DSTPU_LOG_LEVEL")


def run_elastic(args):
    """Supervised launch (reference ``DSElasticAgent``): re-resolve hosts and
    bump the rendezvous port on every restart, so a preempted/replaced host
    list rejoins cleanly; resume correctness rides the universal checkpoint."""
    from ..elasticity.elastic_agent import DSElasticAgent

    def build(attempt):
        hosts = _resolve_hosts(args)  # hostfile re-read: dead hosts drop out
        coordinator = args.master_addr or hosts[0]
        port = args.master_port + attempt  # stale coordinators can't collide
        cmds = build_host_commands(hosts, coordinator, port, args.user_script,
                                   args.user_args, env_passthrough=_ENV_PASSTHROUGH)
        out = []
        for host, argv_h, env in cmds:
            if len(hosts) == 1 and host in ("localhost", "127.0.0.1"):
                out.append((argv_h, {**os.environ, **env}))
            else:
                out.append((_ssh_wrap(host, argv_h, env, args.ssh_port, tty=True),
                            dict(os.environ)))
        return out

    agent = DSElasticAgent(build, max_restarts=args.max_elastic_restarts)
    return agent.run()


def main(argv=None):
    args = parse_args(argv)

    if args.elastic:
        sys.exit(run_elastic(args))

    if not os.path.isfile(args.hostfile):
        logger.info(f"no hostfile at {args.hostfile}; launching on localhost only")
    hosts = _resolve_hosts(args)

    coordinator = args.master_addr or hosts[0]

    if len(hosts) == 1 and not args.force_multi and args.launcher == "ssh":
        # a non-default --launcher skips this shortcut: inside a Slurm/MPI
        # allocation the backend itself does the fan-out even from one host
        env = dict(os.environ)
        env.update({"COORDINATOR_ADDRESS": f"{coordinator}:{args.master_port}",
                    "JAX_NUM_PROCESSES": "1", "JAX_PROCESS_ID": "0"})
        compile_cache.export(env)
        argv = [sys.executable, "-u", args.user_script] + args.user_args
        logger.info(f"single-host launch: {' '.join(argv)}")
        os.execvpe(argv[0], argv, env)  # replaces this process
        return  # unreachable

    if args.launcher != "ssh":
        # backend runners build ONE command that fans out (reference
        # multinode_runner.get_cmd); rank discovery happens in
        # comm.init_distributed from the backend's env
        from .multinode_runner import get_runner
        runner = get_runner(args.launcher, args, {h: 1 for h in hosts}, require=True)
        cmd, env = runner.get_cmd(dict(os.environ), hosts)
        if args.launcher_args:
            cmd = cmd[:1] + shlex.split(args.launcher_args) + cmd[1:]
        logger.info(f"{args.launcher} launch: {' '.join(cmd)}")
        sys.exit(subprocess.call(cmd, env=env))

    cmds = build_host_commands(hosts, coordinator, args.master_port, args.user_script,
                               args.user_args, env_passthrough=_ENV_PASSTHROUGH)
    procs = []
    for host, argv_h, env in cmds:
        full = _ssh_wrap(host, argv_h, env, args.ssh_port)
        logger.info(f"launching on {host}: JAX_PROCESS_ID={env['JAX_PROCESS_ID']}")
        procs.append(subprocess.Popen(full))
    rc = 0
    try:
        for p in procs:
            rc = p.wait() or rc
    except KeyboardInterrupt:  # propagate ctrl-c to the whole job
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait()
        rc = 130
    sys.exit(rc)


if __name__ == "__main__":
    main()
