"""CLI: hvdrun [-np N | -H host1:4,host2:4] [--env K=V ...] -- command ...

The horovodrun analog (the reference at this version has no CLI — launch was
raw mpirun, docs/running.md:22-43; this closes that gap TPU-side). With -H,
workers are spawned through each host's resident hvd-agent daemon
(``python -m horovod_tpu.runner.agent``) — the remote leg the reference got
from Spark executors / mpirun's rsh agent (spark/__init__.py:160-178)."""

from __future__ import annotations

import argparse
import sys


def check_build() -> int:
    """Report what this installation supports (reference
    `horovodrun --check-build`, added upstream after v0.16; here it also
    probes the native engine build and visible accelerators)."""
    import importlib.util

    def has(mod: str) -> bool:
        return importlib.util.find_spec(mod) is not None

    print("horovod_tpu build check")
    native_err = ""
    try:
        from ..cc import lib_path

        path = lib_path()  # triggers the lazy build if needed
        native = f"yes ({path})"
    except Exception as e:  # noqa: BLE001 - report, don't crash
        native = "NO"
        native_err = f"    ({type(e).__name__}: {e})"
    print(f"  native eager engine (C++): {native}")
    if native_err:
        print(native_err)
    for label, mod in (("jax (compiled data plane)", "jax"),
                      ("flax", "flax"), ("optax", "optax"),
                      ("torch (eager binding)", "torch")):
        print(f"  {label}: {'yes' if has(mod) else 'NO'}")
    if has("jax"):
        # Probe devices in a CHILD with a hard timeout: this parent stays
        # off jax (it must not take the chip from the job it diagnoses), and
        # a wedged accelerator runtime or a chip held by another process
        # blocks jax.devices() — a diagnostics command must report that,
        # not hang.
        import subprocess

        # One |-delimited line after a sentinel, so banner noise on stdout
        # (libtpu/absl) can't confuse the parse.
        probe = ("import jax; d = jax.devices(); "
                 "print('HVDPROBE|%d|%s|%s' % (len(d), "
                 "'/'.join(sorted({x.platform for x in d})), "
                 "d[0].device_kind))")
        try:
            out = subprocess.run([sys.executable, "-c", probe],
                                 capture_output=True, text=True, timeout=60)
            line = next((ln for ln in out.stdout.splitlines()
                         if ln.startswith("HVDPROBE|")), None)
            if out.returncode == 0 and line is not None:
                _, n, kinds, kind = line.split("|", 3)
                print(f"  devices: {n} x {kinds} ({kind})")
            else:
                err = (out.stderr.strip().splitlines() or ["no error output"])[-1]
                print(f"  devices: backend init failed ({err[:120]})")
        except subprocess.TimeoutExpired:
            print("  devices: backend init HUNG (>60s) — accelerator "
                  "runtime unreachable, or the chip is held by another "
                  "process (a chip belongs to one process at a time)")
        except Exception as e:  # noqa: BLE001 - report, don't crash
            print(f"  devices: probe failed ({e})")
    print("  collectives: allreduce allgather broadcast alltoall "
          "reducescatter (+ sparse, hierarchical)")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a command on N horovod_tpu worker processes, "
                    "locally (-np) or across hosts via hvd-agents (-H).",
    )
    parser.add_argument("-np", "--num-proc", type=int, default=None,
                        help="number of worker processes (local launch)")
    parser.add_argument("-H", "--hosts", default=None, metavar="host1:4,host2:4",
                        help="remote launch: slots per host, spawned via each "
                             "host's hvd-agent (host[@agent_port][:slots])")
    parser.add_argument("--agent-port", type=int, default=None,
                        help="default hvd-agent port for -H hosts")
    parser.add_argument("--agent-secret-file", default=None,
                        help="file with the shared hvd-agent secret "
                             "(hex or raw; default: HOROVOD_AGENT_SECRET env)")
    parser.add_argument("--env", action="append", default=[],
                        metavar="K=V", help="extra env var for workers")
    parser.add_argument("--jax-distributed", action="store_true",
                        help="federate workers into one JAX distributed "
                             "runtime: hvd.init() in each worker joins the "
                             "launcher-negotiated coordination service, so "
                             "jitted collectives span all workers' chips "
                             "(the N-process pod execution shape)")
    parser.add_argument("--check-build", action="store_true",
                        help="print what this installation can do (native "
                             "engine, frameworks, devices) and exit — the "
                             "later-reference `horovodrun --check-build`")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command to run (prefix with --)")
    args = parser.parse_args(argv)
    if args.check_build:
        return check_build()
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        parser.error("no command given; usage: -np 4 -- python train.py")
    if args.num_proc is None and args.hosts is None:
        parser.error("one of -np or -H is required")
    extra_env = {}
    for kv in args.env:
        k, _, v = kv.partition("=")
        extra_env[k] = v

    agent_secret = None
    if args.agent_secret_file:
        from .agent import _load_secret

        agent_secret = _load_secret(args.agent_secret_file)

    from . import run_command

    return run_command(command, num_proc=args.num_proc, env=extra_env,
                       hosts=args.hosts, agent_port=args.agent_port,
                       agent_secret=agent_secret,
                       jax_distributed=args.jax_distributed)


if __name__ == "__main__":
    sys.exit(main())
