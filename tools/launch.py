#!/usr/bin/env python
"""Local multi-process launcher (ref: tools/launch.py, local mode).

The reference's launcher boots a scheduler + parameter servers + workers and
exports the DMLC_* env contract. Here there are no servers: every worker is
symmetric, joining one jax.distributed runtime whose coordinator is worker 0.
This launcher runs N workers on this machine (the analog of the reference's
``launch.py -n N --launcher local``) — on a real TPU pod each host runs one
process and jax.distributed autodetects, so no launcher is needed there.

Usage::

    python tools/launch.py -n 2 python my_train_script.py

Each worker gets MXTPU_COORDINATOR / MXTPU_NUM_PROCESSES / MXTPU_PROCESS_ID
(and the reference-compatible DMLC_* names), which ``mxtpu.distributed.init()``
reads. Workers get JAX_PLATFORMS=cpu (the default): a chip belongs to one
process at a time, so N local workers cannot share this host's
accelerators, and ``--no-cpu`` is refused for N > 1.
"""
import argparse
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--cpu", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="force JAX_PLATFORMS=cpu in workers (default); "
                         "--no-cpu lets the ONE worker of -n 1 use the "
                         "accelerator")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if not args.cpu and args.num_workers > 1:
        # every local worker would open every chip of this host, and a
        # chip belongs to one process at a time: the second worker fails
        # or hangs at backend start-up. One process drives all the chips
        # of a host (Trainer(mesh=...)); several processes are several
        # hosts.
        ap.error("--no-cpu with -n %d: the chips of one host belong to one "
                 "process at a time, so N local accelerator workers cannot "
                 "start. Use one process over a device mesh on this host, "
                 "or one worker per host." % args.num_workers)

    port = _free_port()
    procs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update({
            "MXTPU_COORDINATOR": "127.0.0.1:%d" % port,
            "MXTPU_NUM_PROCESSES": str(args.num_workers),
            "MXTPU_PROCESS_ID": str(rank),
            # reference-compatible spellings (tools/launch.py env contract)
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_WORKER_ID": str(rank),
            "DMLC_ROLE": "worker",
        })
        if args.cpu:
            env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(args.command, env=env))

    rc = 0
    try:
        for p in procs:
            p.wait()
            rc = rc or p.returncode
            if rc:
                break  # one worker failed: take the rest down (a partial
                       # world would hang in the next collective anyway)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    sys.exit(rc)


if __name__ == "__main__":
    main()
