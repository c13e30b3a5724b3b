#!/usr/bin/env python
"""Distributed job launcher.

Reference: tools/launch.py (dmlc_tracker ssh/mpi/yarn/sge + local).  The
TPU-native job has no scheduler/server roles — this launcher spawns N
identical worker processes (local or via ssh) with the env contract consumed
by mxnet_tpu.kvstore_dist (DMLC_* names kept for CLI compatibility):

  python tools/launch.py -n 4 --launcher local python train.py ...

Local mode is the test harness for multi-host logic on one machine
(reference tests/nightly pattern: N processes over loopback).  The
launcher itself never imports JAX; every worker does, with the inherited
environment.  A chip belongs to one process, so on a host with one chip
run local workers on the CPU (``JAX_PLATFORMS=cpu`` in the environment,
as tests/test_dist.py does): N workers that all reach for the one chip
fail at start-up or hang.
"""
import argparse
import os
import shlex
import socket
import subprocess
import sys


def _dmlc_env(num_workers, root_host, port):
    """The worker env contract (kvstore_dist.py), in one place."""
    return [("DMLC_PS_ROOT_URI", str(root_host)),
            ("DMLC_PS_ROOT_PORT", str(port)),
            ("DMLC_NUM_WORKER", str(num_workers)),
            ("DMLC_ROLE", "worker")]


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def launch_local(num_workers, command, env_extra=None):
    port = _free_port()
    procs = []
    for rank in range(num_workers):
        env = dict(os.environ)
        env.update(env_extra or {})
        env.update({
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(num_workers),
            "DMLC_WORKER_ID": str(rank),
            "DMLC_ROLE": "worker",
        })
        procs.append(subprocess.Popen(command, env=env))
    codes = [p.wait() for p in procs]
    return next((c for c in codes if c), 0)


def launch_ssh(hosts, num_workers, command, port=None):
    # _free_port() probes THIS machine, which says nothing about hosts[0];
    # default to a fixed high port and let --port override on conflict
    port = port or 29500
    root = hosts[0]
    procs = []
    for rank in range(num_workers):
        host = hosts[rank % len(hosts)]
        envs = " ".join("%s=%s" % kv for kv in
                        _dmlc_env(num_workers, root, port)
                        + [("DMLC_WORKER_ID", str(rank))])
        cmd = ["ssh", "-o", "StrictHostKeyChecking=no", host,
               "cd %s; env %s %s" % (shlex.quote(os.getcwd()), envs,
                                     shlex.join(command))]
        procs.append(subprocess.Popen(cmd))
    codes = [p.wait() for p in procs]
    return next((c for c in codes if c), 0)


def build_mpi_command(num_workers, command, root_host, port, hostfile=None):
    """mpirun invocation (dmlc_tracker/mpi.py analog): ranks map to
    DMLC_WORKER_ID via the launched shim reading OMPI/PMI rank vars."""
    shim = ("DMLC_WORKER_ID=${OMPI_COMM_WORLD_RANK:-${PMI_RANK:-0}} "
            + shlex.join(command))
    envs = []
    for k, v in _dmlc_env(num_workers, root_host, port):
        envs += ["-x", "%s=%s" % (k, v)]
    hosts = ["--hostfile", hostfile] if hostfile else []
    return (["mpirun", "--allow-run-as-root", "-n", str(num_workers)]
            + hosts + envs + ["bash", "-c", shim])


def build_sge_command(num_workers, command, root_host, port, queue,
                      jobname="mxtpu"):
    """qsub array-job invocation (dmlc_tracker/sge.py analog): one task per
    worker; SGE_TASK_ID (1-based) becomes DMLC_WORKER_ID."""
    envs = ",".join("%s=%s" % kv
                    for kv in _dmlc_env(num_workers, root_host, port))
    shim = ("DMLC_WORKER_ID=$((SGE_TASK_ID-1)) " + shlex.join(command))
    return (["qsub", "-N", jobname, "-q", queue, "-t",
             "1-%d" % num_workers, "-v", envs, "-b", "y", "-sync", "y",
             "-cwd", "bash", "-c", shim])


def build_yarn_command(num_workers, command, root_host, port,
                       jobname="mxtpu"):
    """yarn distributed-shell invocation (dmlc_tracker/yarn.py analog);
    the distributed shell exports YARN_SHELL_ID (1-based) per container —
    that is the rank."""
    shim = ("DMLC_WORKER_ID=$((${YARN_SHELL_ID:-1}-1)) "
            + shlex.join(command))
    jar = os.environ.get("YARN_DSHELL_JAR",
                         "hadoop-yarn-applications-distributedshell.jar")
    cmd = ["yarn", "jar", jar, "-jar", jar, "-appname", jobname,
           "-num_containers", str(num_workers)]
    for k, v in _dmlc_env(num_workers, root_host, port):
        cmd += ["-shell_env", "%s=%s" % (k, v)]
    return cmd + ["-shell_command", shim]


def main():
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", required=True, type=int,
                        help="number of worker processes")
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="ignored (no PS roles on TPU; kept for CLI compat)")
    parser.add_argument("--launcher", default="local",
                        choices=["local", "ssh", "mpi", "sge", "yarn"])
    parser.add_argument("-H", "--hostfile", default=None,
                        help="hostfile for ssh launcher, one host per line")
    parser.add_argument("--port", type=int, default=None,
                        help="coordinator port on the first host")
    parser.add_argument("--root-host", default=None,
                        help="coordinator host for mpi/sge/yarn launchers "
                             "(default: this machine's hostname)")
    parser.add_argument("--queue", default="all.q",
                        help="SGE queue name (sge launcher)")
    parser.add_argument("--dry-run", action="store_true",
                        help="print the scheduler submit command instead "
                             "of executing it (mpi/sge/yarn)")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, args.command))
    if args.launcher == "ssh":
        hosts = [l.strip() for l in open(args.hostfile) if l.strip()]
        sys.exit(launch_ssh(hosts, args.num_workers, args.command,
                            args.port))
    root = args.root_host or socket.gethostname()
    port = args.port or 29500
    if args.launcher == "mpi":
        cmd = build_mpi_command(args.num_workers, args.command, root, port,
                                hostfile=args.hostfile)
    elif args.launcher == "sge":
        cmd = build_sge_command(args.num_workers, args.command, root, port,
                                args.queue)
    else:
        cmd = build_yarn_command(args.num_workers, args.command, root, port)
    if args.dry_run:
        print(" ".join(cmd))
        sys.exit(0)
    sys.exit(subprocess.call(cmd))


if __name__ == "__main__":
    main()
