"""CLI entry point: `python -m repro.server [--port N] [--clock wall]`.

Builds the engine described by the flags (the arch's smoke preset, or
its published widths in bf16 with `--full`), binds, prints
`LISTENING <port>` on stdout (the CI smoke job and Makefile `serve`
target wait for that line), and serves until SIGTERM/SIGINT — which
triggers a graceful drain: new streams get 503, live ones finish within
--drain-timeout, then the process exits 0. If the engine fails, the
server stops and the process exits 1.
"""
from __future__ import annotations

import argparse
import signal
import sys
import threading

from repro.launch.compile_cache import enable_compile_cache
from repro.server.app import ServerConfig, ServingServer


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m repro.server",
                                description="Andes HTTP/SSE serving frontend")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 = OS-assigned (printed as LISTENING <port>)")
    p.add_argument("--arch", default="llama3-8b")
    p.add_argument("--full", action="store_true",
                   help="published widths in bf16 (default: smoke preset)")
    p.add_argument("--clock", choices=("wall", "virtual"), default="wall")
    p.add_argument("--scheduler", default="andes")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-seq", type=int, default=64)
    p.add_argument("--queue-depth", type=int, default=256)
    p.add_argument("--drain-timeout", type=float, default=10.0)
    p.add_argument("--no-warmup", action="store_true")
    args = p.parse_args(argv)

    enable_compile_cache()
    cfg = ServerConfig(host=args.host, port=args.port, arch=args.arch,
                       full=args.full,
                       clock=args.clock, scheduler=args.scheduler,
                       num_slots=args.slots, max_seq=args.max_seq,
                       queue_depth=args.queue_depth,
                       drain_timeout=args.drain_timeout,
                       warmup=not args.no_warmup)
    server = ServingServer(cfg)
    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    port = server.start()
    print(f"LISTENING {port}", flush=True)
    while not stop.wait(timeout=0.5) and server.failure is None:
        pass
    phase = server.shutdown(drain=True)
    print(f"DRAINED {phase}", flush=True)
    return 0 if phase == "done" else 1


if __name__ == "__main__":
    sys.exit(main())
