"""Launch a multi-process / multi-host SPMD training job.

Parity target: tools/launch.py (the dmlc-tracker front door). The
reference starts a ps-lite scheduler plus server/worker processes; the
TPU-native job has no server role — every process is an SPMD worker
that rendezvouses at a coordinator via
`mxnet_tpu.parallel.init_distributed()`, which reads the MXNET_TPU_*
environment this launcher exports.

  local mode:  python tools/launch.py -n 4 python train.py ...
  ssh mode:    python tools/launch.py -n 8 -H hostfile python train.py ...

Hostfile: one host per line (optionally "host slots=K"); processes are
assigned round-robin. --launcher local additionally forces a virtual
CPU device per process so -n workers can be smoke-tested on one
machine without TPUs.
"""

import argparse
import os
import shlex
import subprocess
import sys


def parse_hostfile(path):
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split()
            slots = 1
            for p in parts[1:]:
                if p.startswith("slots="):
                    slots = int(p.split("=", 1)[1])
            hosts.extend([parts[0]] * slots)
    return hosts


def worker_env(args, proc_id, base=None):
    env = dict(base if base is not None else os.environ)
    env.update({
        "MXNET_TPU_COORDINATOR": args.coordinator,
        "MXNET_TPU_NUM_PROC": str(args.num_workers),
        "MXNET_TPU_PROC_ID": str(proc_id),
        # reference-compatible aliases, for scripts reading DMLC_*
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_WORKER_ID": str(proc_id),
    })
    if args.launcher == "local":
        # each local process simulates one device so collective code
        # paths run without hardware; OVERRIDE any inherited accelerator
        # platform — a chip belongs to one process, so N local
        # processes must never reach for one
        env["JAX_PLATFORMS"] = "cpu"
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=1")
    return env


def launch_local(args, command):
    procs = []
    for i in range(args.num_workers):
        procs.append(subprocess.Popen(command,
                                      env=worker_env(args, i)))
    code = 0
    for p in procs:
        code = p.wait() or code
    return code


def _quote_path(token):
    """shlex.quote, but keep a leading ~/ outside the quotes so the
    remote shell still expands the home directory."""
    if token == "~":
        return token
    if token.startswith("~/"):
        return "~/" + shlex.quote(token[2:])
    return shlex.quote(token)


def launch_ssh(args, command):
    hosts = parse_hostfile(args.hostfile)
    if len(hosts) < args.num_workers:
        print("hostfile provides %d slots for %d workers"
              % (len(hosts), args.num_workers), file=sys.stderr)
        return 1
    procs = []
    for i in range(args.num_workers):
        exports = " ".join(
            "%s=%s" % (k, shlex.quote(v))
            for k, v in worker_env(args, i, base={}).items())
        remote = "cd %s && env %s %s" % (
            _quote_path(args.remote_cwd) if args.remote_cwd else "~",
            exports, " ".join(_quote_path(c) for c in command))
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", hosts[i], remote]))
    code = 0
    for p in procs:
        code = p.wait() or code
    return code


def main():
    parser = argparse.ArgumentParser(
        description="launch a distributed job",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--launcher", type=str, default=None,
                        choices=("local", "ssh"),
                        help="default: ssh when a hostfile is given")
    parser.add_argument("--coordinator", type=str, default="127.0.0.1:8476",
                        help="host:port every worker rendezvouses at")
    parser.add_argument("--remote-cwd", type=str, default=None)
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()

    if not args.command:
        parser.error("no command given")
    if args.launcher is None:
        args.launcher = "ssh" if args.hostfile else "local"
    if args.launcher == "ssh" and not args.hostfile:
        parser.error("ssh launcher needs --hostfile")

    if args.launcher == "local":
        return launch_local(args, args.command)
    return launch_ssh(args, args.command)


if __name__ == "__main__":
    sys.exit(main())
